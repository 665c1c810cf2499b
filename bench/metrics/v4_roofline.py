"""v4_roofline: kernel v4 (``pvq_attn_q``) in the decode program, as a
share of its roofline, in %: the least time of reading every active
slot's packed K and V (its completed pages only) in the window's decode
steps (``harness.work``), over the device time of the v4 kernel events
inside the decode program."""

from harness import work
from harness.readers import ops_ns

PROGRAM = "jit__decode_fn"
#: the Mosaic call of pvq_attn_q: XLA names the custom call after the
#: jitted wrapper (``pvq_attn_q.8`` in the compiled decode program)
KERNEL = r"^%?pvq_attn_q(\.\d+)+$"


def read(run):
    ns = ops_ns(run, PROGRAM, KERNEL)
    steps = run.steps
    if ns <= 0 or not steps or run.peaks is None:
        return None
    e = run.config["engine"]
    need = sum(
        work.v4_step(run.arch, [work.packed_len(n, e["page"]) for n in s.lengths],
                     e["kv_group"], run.peaks)
        for s in steps
    )
    return 100.0 * need / (ns / 1e9)
