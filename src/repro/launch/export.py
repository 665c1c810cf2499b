"""Export CLI: model params -> ``.pvqz`` compressed artifact (paper §VI).

    # a transformer config (packed per the serving quantization policy):
    PYTHONPATH=src python -m repro.launch.export --arch smollm-360m --reduced \
        --out model.pvqz

    # one of the paper's own nets (§VII; FC layers at their Table N/K ratios):
    PYTHONPATH=src python -m repro.launch.export --paper-net A --out a.pvqz \
        --max-bits-per-weight 2.0

Encodes the pytree ONCE into ``PackedPVQ`` leaves (exactly what serving
uses), entropy-codes the pulse streams into the single-file container, and
prints the per-leaf bits/weight report.  ``--max-bits-per-weight`` turns the
report into a gate (exit 1 when the packed artifact misses the budget) —
CI uses it to pin the §VI compression claim to real artifacts.

``repro.launch.serve --artifact model.pvqz`` consumes the file, restoring
the identical pulses/scales with no re-encode.
"""

from __future__ import annotations

import argparse
import json
import time

import jax

from repro.checkpoint.artifact import write_pvqz
from repro.runtime.caches import enable_compile_cache


def export_arch(args) -> tuple:
    """(params pytree with PackedPVQ leaves, meta) for a transformer config."""
    from repro.configs import get_config
    from repro.core.packed import quantize_params
    from repro.core.quantize import QuantPolicy
    from repro.nn.models import build_model

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed), max_seq=args.max_seq)
    policy = QuantPolicy(
        rules=(("embedding", cfg.pvq.n_over_k_embed, cfg.pvq.group),
               ("kernel|experts", args.n_over_k, cfg.pvq.group)),
        scale_mode="ls",
    )
    qparams = quantize_params(params, policy)
    meta = {"kind": "arch", "arch": cfg.name, "reduced": bool(args.reduced),
            "n_over_k": args.n_over_k, "seed": args.seed}
    return qparams, meta


def export_paper_net(args) -> tuple:
    """(params with packed FC kernels, meta) for one of the §VII nets.

    FC kernels are packed at each layer's Table N/K ratio via the same
    ``pvq_quantize_dense`` path the kernel-serving tests use; conv layers
    (consumed as dense einsums) stay raw.
    """
    from repro.configs.paper_nets import PAPER_NETS
    from repro.nn.sequential import SequentialNet

    net = SequentialNet(PAPER_NETS[args.paper_net])
    params = net.init(jax.random.PRNGKey(args.seed))
    kparams = net.pvq_kernel_encode(params, group=args.group)
    merged = dict(params)
    merged.update(kparams)
    meta = {"kind": "paper_net", "net": args.paper_net, "group": args.group,
            "seed": args.seed}
    return merged, meta


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    src = ap.add_mutually_exclusive_group()
    src.add_argument("--arch", default=None, help="transformer config name")
    src.add_argument("--paper-net", default=None, choices=("A", "B", "C", "D"),
                     help="one of the paper's §VII experiment nets")
    ap.add_argument("--out", required=True, help="output .pvqz path")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--n-over-k", type=float, default=1.0,
                    help="kernel N/K ratio (arch mode; paper nets use their "
                    "per-layer Table ratios)")
    ap.add_argument("--group", type=int, default=256,
                    help="PVQ group size for paper-net FC kernels")
    ap.add_argument("--codec", default="auto",
                    help="pulse codec: auto|golomb|rle|enum|nibble|int8")
    ap.add_argument("--chunk", type=int, default=1024,
                    help="symbols per decodable chunk of the entropy streams")
    ap.add_argument("--max-seq", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-bits-per-weight", type=float, default=None,
                    help="fail (exit 1) if the packed artifact exceeds this")
    ap.add_argument("--max-expert-bits-per-weight", type=float, default=None,
                    help="fail (exit 1) if the MoE expert leaves alone "
                    "(*_experts pulse streams + scales) exceed this")
    args = ap.parse_args()
    enable_compile_cache()
    if not args.arch and not args.paper_net:
        args.arch = "smollm-360m"

    t0 = time.time()
    if args.paper_net:
        qparams, meta = export_paper_net(args)
    else:
        qparams, meta = export_arch(args)
    encode_s = time.time() - t0

    t0 = time.time()
    report = write_pvqz(args.out, qparams, codec=args.codec, chunk=args.chunk,
                        meta=meta)
    report["encode_s"] = round(encode_s, 2)
    report["write_s"] = round(time.time() - t0, 2)

    # aggregate view of the MoE expert bank (the weight-bytes headline):
    # bits/weight over the expert leaves only, weighted by their numel
    import re

    from repro.core.packed import EXPERT_LEAF_REGEX

    expert = {k: v for k, v in report["leaves"].items()
              if re.search(EXPERT_LEAF_REGEX, k) and v.get("codec") != "raw"}
    if expert:
        numel = sum(v["numel"] for v in expert.values())
        bits = sum(v["bits_per_weight"] * v["numel"] for v in expert.values())
        report["expert_leaves"] = len(expert)
        report["expert_numel"] = numel
        report["expert_bits_per_weight"] = round(bits / max(numel, 1), 4)
    print(json.dumps(report, indent=1))

    if (
        args.max_bits_per_weight is not None
        and report["bits_per_weight"] > args.max_bits_per_weight
    ):
        print(
            f"FAIL: {report['bits_per_weight']} bits/weight exceeds the "
            f"--max-bits-per-weight {args.max_bits_per_weight} gate"
        )
        return 1
    if args.max_expert_bits_per_weight is not None:
        ebpw = report.get("expert_bits_per_weight")
        if ebpw is None:
            print("FAIL: --max-expert-bits-per-weight set but no packed "
                  "*_experts leaves were exported")
            return 1
        if ebpw > args.max_expert_bits_per_weight:
            print(
                f"FAIL: {ebpw} expert bits/weight exceeds the "
                f"--max-expert-bits-per-weight {args.max_expert_bits_per_weight} gate"
            )
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
