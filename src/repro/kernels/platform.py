"""Interpret or compile a Pallas call, by the platform it is lowered for.

A Pallas kernel compiles to Mosaic on a TPU and can only be interpreted on
the CPU.  The choice is made when the program is lowered, not when Python
runs: a program lowered for a described TPU from a CPU-only process (the
compile rehearsals of tests/test_tpu_compile.py) gets the compiled kernel,
and the same code run on the CPU gets the interpreter.  Nothing else
chooses interpret mode.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax


def by_platform(call: Callable, *args):
    """``call(interpret, *args)`` with ``interpret`` True exactly when the
    program is lowered for the CPU."""
    return jax.lax.platform_dependent(
        *args, cpu=functools.partial(call, True),
        default=functools.partial(call, False))
