"""Compile each configuration's programs for a described TPU v5e, without
a chip: the weight maker, the decode step at the cell's slots and pool,
the chunk step, and the largest whole-prompt prefill with its graft.
Prints each program's ``memory_analysis()`` and its Mosaic kernel count.

    JAX_PLATFORMS=cpu python3 bench/aot_compile.py [config ...]

Nothing runs, so this says nothing about results or times; what the
chip's compiler refuses (tiling, VMEM, a program that does not fit) shows
here at no chip time.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH.parent / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import spec as spec_lib, weights
    from repro.core.quantize import ActQuant, KVQuant, act_quant_scope, kv_quant_scope
    from repro.kernels import ops
    from repro.launch.engine import PVQEngine, bucket_len
    from repro.nn.models import build_model

    jax.config.update("jax_enable_compilation_cache", False)
    ops._on_tpu = lambda: True  # the dispatch asks the backend, which is the CPU here
    one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    spec = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)  # noqa: E731
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)  # noqa: E731
    b = spec_lib.benchmark()
    names = argv or [c["name"] for c in b["configs"]]
    for c in b["configs"]:
        if c["name"] not in names:
            continue
        config = spec_lib._json(spec_lib.ROOT / c["file"])
        e = config["engine"]

        def report(what, compiled):
            m = compiled.memory_analysis()
            print(f"{c['name']} {what}: mosaic {compiled.as_text().count('tpu_custom_call')}, "
                  f"args {m.argument_size_in_bytes}, out {m.output_size_in_bytes}, "
                  f"temp {m.temp_size_in_bytes}, code {m.generated_code_size_in_bytes}", flush=True)

        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
        maker = weights.maker(weights.program_tree(config))
        report("weights", maker.lower(key).compile())
        params = jax.eval_shape(maker, key)
        with act_quant_scope(ActQuant(mode="per_row")), kv_quant_scope(
            KVQuant(block=e["page"], group=e["kv_group"], k=e["kv_pulses"])
        ):
            model = build_model(weights.model_config(config))
            ns, mp = e["n_slots"], bucket_len(e["max_len"], e["page"]) // e["page"]
            cache = jax.eval_shape(lambda: model.init_paged_cache(ns, e["n_pages"], mp))
            # a one-page engine stands in for the real one, whose pool would be
            # allocated on the host here; the programs see the real shapes
            eng = PVQEngine(model, params, n_slots=1, max_len=e["page"], n_pages=1,
                            prefill_chunk=e["prefill_chunk"], prefix_cache=e["prefix_cache"])
            eng.n_slots, eng.max_pages, eng.alloc.n_pages = ns, mp, e["n_pages"]
            report("decode", jax.jit(eng._decode_fn).lower(
                spec(params), spec(cache), i32(ns, 1), i32(ns), i32(ns, mp), i32(ns)).compile())
            ctk = e["prefill_chunk"] * e["page"]
            report("chunk", jax.jit(eng._chunk_fn).lower(
                spec(params), spec(cache), i32(1, ctk), i32(), i32(), i32(ctk // e["page"]), i32(),
                i32(ns, mp)).compile())
            with kv_quant_scope(None):
                pre = jax.jit(eng._prefill_fn).lower(spec(params), i32(1, ctk), i32(1))
            report("prefill", pre.compile())
            with kv_quant_scope(None):
                pre_out = jax.eval_shape(eng._prefill_fn, params, jax.ShapeDtypeStruct((1, ctk), jnp.int32),
                                         jax.ShapeDtypeStruct((1,), jnp.int32))
            report("graft", jax.jit(eng._graft_fn).lower(
                spec(cache), spec(pre_out[1]), i32(1), i32(1, ctk // e["page"]), i32(1)).compile())
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
