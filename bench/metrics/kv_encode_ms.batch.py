"""kv_encode_ms.batch: device time of the PVQ encode of completed KV pages,
per execution of the engine's decode program, in ms.  The encode is the
one conditional op of the decode program (``PagedKV.append``'s
``lax.cond``, taken whenever a slot completes a page), matched by its
opcode because XLA numbers it anew in every compile (``cond.28``)."""

from harness.readers import ops_ns

PROGRAM = "jit__decode_fn"
OPCODE = r"^conditional$"


def read(run):
    ns = ops_ns(run, PROGRAM, OPCODE)
    calls = run.trace["modules"].get(PROGRAM, {}).get("calls", 0) if run.trace else 0
    if ns <= 0 or calls <= 0:
        return None
    return ns / calls / 1e6
