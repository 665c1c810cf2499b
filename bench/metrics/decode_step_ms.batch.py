"""decode_step_ms: mean device time of one execution of the engine's
decode program (``PVQEngine._decode``, named after the jitted
``_decode_fn``) in the traced window."""

from harness.readers import module_ms

PROGRAM = "jit__decode_fn"


def read(run):
    return module_ms(run, PROGRAM)
