"""output_tokens_per_s: tokens served inside the window over the window's
length (host clock; every token is stamped when the call that produced it
returns)."""

from harness.readers import tokens_in_window


def read(run):
    return tokens_in_window(run) / run.window_s
