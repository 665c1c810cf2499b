"""v3_roofline: kernel v3 (``pvq_matmul_q``) in the decode program, as a
share of its roofline, in %: the least time of every layer GEMM the
window's decode steps needed, at each step's active slots (logical
shapes, ``harness.work``), over the device time of the v3 kernel events
inside the decode program."""

from harness import work
from harness.readers import ops_ns

PROGRAM = "jit__decode_fn"
#: the Mosaic call of pvq_matmul_q: XLA names the custom call after the
#: jitted wrapper (``pvq_matmul_q.58`` in the compiled decode program)
KERNEL = r"^%?pvq_matmul_q(\.\d+)+$"


def read(run):
    ns = ops_ns(run, PROGRAM, KERNEL)
    steps = run.steps
    if ns <= 0 or not steps or run.peaks is None:
        return None
    group = run.config["weights"]["group"]
    need = sum(work.v3_step(run.arch, len(s.lengths), group, run.peaks) for s in steps)
    return 100.0 * need / (ns / 1e9)
