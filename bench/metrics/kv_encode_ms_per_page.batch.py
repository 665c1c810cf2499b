"""kv_encode_ms_per_page.batch: device time of the PVQ encode of KV pages
in the traced window, per page that the window's decode steps completed,
in ms.  The encode is the decode program's one conditional op, as
``kv_encode_ms.batch`` reads it; a slot completes a page in a step when
its length after the step (``Step.lengths``) is a multiple of the page.
Today the encode runs over every slot's tail ring whenever any slot
completes a page, so the reading is about ``n_slots`` rings per page
needed; encoding only the completing slots brings it down to about one."""

from harness.readers import ops_ns

PROGRAM = "jit__decode_fn"
OPCODE = r"^conditional$"


def read(run):
    page = run.config["engine"]["page"]
    pages = sum(1 for s in run.steps for n in s.lengths if n % page == 0)
    ns = ops_ns(run, PROGRAM, OPCODE)
    if ns <= 0 or pages <= 0:
        return None
    return ns / pages / 1e6
