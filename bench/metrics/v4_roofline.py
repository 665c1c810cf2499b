"""v4_roofline: kernel v4 (``pvq_attn_q``) in the decode program, as a
share of its roofline, in %: the least time of reading every active
slot's packed K and V (its completed pages only) in the window's decode
steps (the reference module's ``v4_step``), over the device time of the
v4 kernel events inside the decode program."""

from harness import work
from harness.readers import ops_ns

PROGRAM = "jit__decode_fn"
#: the Mosaic call of pvq_attn_q: XLA names the custom call after the
#: jitted wrapper (``pvq_attn_q.8`` in the compiled decode program)
KERNEL = r"^%?pvq_attn_q(\.\d+)+$"

#: the reference module's count this reads; ``spec.cell`` checks it is there
NEEDS = ("v4_step",)


def read(run):
    count = getattr(run.ref, "v4_step", None)
    ns = ops_ns(run, PROGRAM, KERNEL)
    steps = run.steps
    if count is None or ns <= 0 or not steps or run.peaks is None:
        return None
    e = run.config["engine"]
    need = sum(
        count(run.arch, [work.packed_len(n, e["page"]) for n in s.lengths], e["kv_group"], run.peaks)
        for s in steps
    )
    return 100.0 * need / (ns / 1e9)
