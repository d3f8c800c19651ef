"""Pallas TPU kernels.

Each module provides a jittable, differentiable entry point. On a TPU backend
the kernels are compiled by Mosaic. On the CPU backend they run only through
the Pallas interpreter, and only when PADDLE_TPU_PALLAS_INTERPRET=1 asks for
it — the analog of the reference testing CUDA kernels against NumPy oracles
(test/legacy_test/op_test.py). Interpret mode is a CPU-test facility: with a
TPU backend the variable is an error, because a leaked setting would turn
every kernel into an interpreted one with no message.
"""

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .autotune import KERNEL_NAMES


def interpret_mode() -> bool:
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") != "1":
        return False
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "PADDLE_TPU_PALLAS_INTERPRET=1 with a TPU backend: interpret "
            "mode is for CPU tests only and would silently replace every "
            "Mosaic-compiled kernel with the Pallas interpreter. Unset it.")
    return True


def kernels_available() -> bool:
    """The one dispatch rule for every Pallas kernel: Mosaic on a TPU
    backend, the interpreter on CPU when asked for, nothing otherwise
    (pallas_call rejects compile mode on a bare CPU backend). The backend
    query is not guarded: a backend that fails to initialise must fail the
    caller, not select the jnp composite."""
    return interpret_mode() or jax.default_backend() == "tpu"


def mxu_dot(a, b, dimension_numbers, **kw):
    """`lax.dot_general` as the kernels feed the MXU. bf16/int8 operands have
    one MXU precision, and Mosaic refuses to compile any other for them
    ("Bad lhs type" under an ambient jax_default_matmul_precision=highest,
    which the test suite sets); only f32 operands follow the ambient
    precision, as they do in XLA."""
    if a.dtype != jnp.float32:
        kw.setdefault("precision", jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, dimension_numbers, **kw)


def named_pallas_call(name, kernel, **kw):
    """`pl.pallas_call(kernel, **kw)` under `name`, which must be in
    `autotune.KERNEL_NAMES`. The name is the kernel's `name=` (the compiled
    custom call is the HLO instruction `%<name>.N`, which is what a profiler
    trace shows) and the `jax.named_scope` the call runs under. Metadata
    only: the compiled program's operations do not change."""
    if name not in KERNEL_NAMES:
        raise ValueError(f"{name!r} is not in autotune.KERNEL_NAMES")
    call = pl.pallas_call(kernel, name=name, **kw)

    def run(*args):
        with jax.named_scope(name):
            return call(*args)

    return run
