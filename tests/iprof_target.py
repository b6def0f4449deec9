"""Tiny traced workload used by the iprof CLI tests."""

import sys

import jax.numpy as jnp

from repro.core import collective_span, traced_jit, train_step_span

_f = traced_jit(lambda x: (x * x).sum(), name="square_sum")


def main():
    x = jnp.arange(64.0)
    for step in range(3):
        with train_step_span(step, 2, 32) as sp:
            sp.outs["loss"] = float(_f(x))
            sp.outs["grad_norm"] = 1.0
        with collective_span("all_reduce", 256, "data", 4):
            pass


def returns_2():
    main()
    return 2


def exits_2():
    main()
    sys.exit(2)
