"""Prompt tokens whose prefill completed inside the window plus output
tokens emitted inside it, over the window's length."""
from bench.lib.judge import tokens_per_s


def read(run):
    return tokens_per_s(run.outs, run.t0, run.t_end)
