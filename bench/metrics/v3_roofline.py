"""v3_roofline: kernel v3 (``pvq_matmul_q``) in the decode program, as a
share of its roofline, in %: the least time of every layer GEMM the
window's decode steps needed, at each step's active slots (logical
shapes, the reference module's ``v3_step``), over the device time of the
v3 kernel events inside the decode program."""

from harness.readers import ops_ns

PROGRAM = "jit__decode_fn"
#: the Mosaic call of pvq_matmul_q: XLA names the custom call after the
#: jitted wrapper (``pvq_matmul_q.58`` in the compiled decode program)
KERNEL = r"^%?pvq_matmul_q(\.\d+)+$"

#: the reference module's count this reads; ``spec.cell`` checks it is there
NEEDS = ("v3_step",)


def read(run):
    count = getattr(run.ref, "v3_step", None)
    ns = ops_ns(run, PROGRAM, KERNEL)
    steps = run.steps
    if count is None or ns <= 0 or not steps or run.peaks is None:
        return None
    group = run.config["weights"]["group"]
    need = sum(count(run.arch, len(s.lengths), group, run.peaks) for s in steps)
    return 100.0 * need / (ns / 1e9)
