"""paddle_tpu: a TPU-native deep-learning framework.

Brand-new design with the capability surface of the PaddlePaddle reference
built on JAX/XLA/Pallas:

- Tensors wrap jax.Array; XLA owns kernels, layouts, memory (replacing the phi
  kernel registry / allocator stack).
- Eager autograd is a VJP tape (framework/core.py); functional/jit training
  uses jax.grad through paddle_tpu.jit.
- Distributed = named mesh axes + compiled ICI/DCN collectives (paddle_tpu.distributed).
"""

from __future__ import annotations

from . import autograd, framework, tensor
from .autograd import PyLayer, enable_grad, grad, no_grad, set_grad_enabled
from .framework import (
    Parameter,
    Tensor,
    get_default_dtype,
    get_flags,
    load,
    save,
    seed,
    set_default_dtype,
    set_flags,
    to_tensor,
)
from .framework.core import is_grad_enabled
from .framework.dtype import (  # noqa: F401
    bfloat16,
    bool_ as bool,  # noqa: A001
    complex64,
    complex128,
    float16,
    float32,
    float64,
    int8,
    int16,
    int32,
    int64,
    uint8,
)
from .framework.random import get_rng_state, set_rng_state
from .tensor import *  # noqa: F401,F403
from .tensor import linalg  # namespace: paddle.linalg.*
from .tensor.logic import is_tensor


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_custom_device(name: str) -> bool:
    return name in ("tpu",)


def device_count() -> int:
    import jax

    return jax.device_count()


def set_device(device: str):
    # single-controller JAX owns placement; accepted for API parity
    return device


def get_device() -> str:
    import jax

    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


class CPUPlace:
    pass


class TPUPlace:
    def __init__(self, idx: int = 0):
        self.idx = idx


CUDAPlace = TPUPlace  # scripts that name CUDAPlace get the accelerator

# subpackages added as they are built (M2+)
from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import amp  # noqa: E402
from .nn.layer.layers import ParamAttr  # noqa: E402
from . import io  # noqa: E402
from . import metric  # noqa: E402
from . import vision  # noqa: E402
from . import jit  # noqa: E402
from . import hapi  # noqa: E402
from .hapi import Model, flops, summary  # noqa: E402
from . import distributed  # noqa: E402
from .distributed import DataParallel  # noqa: E402
from . import incubate  # noqa: E402
from . import inference  # noqa: E402
from . import profiler  # noqa: E402
from . import observability  # noqa: E402
from . import device  # noqa: E402
from . import fft  # noqa: E402
from . import distribution  # noqa: E402
from . import static  # noqa: E402
from .static import disable_static, enable_static  # noqa: E402
from . import utils  # noqa: E402
from . import sparse  # noqa: E402
from . import quantization  # noqa: E402
from . import audio  # noqa: E402
from . import text  # noqa: E402
from . import onnx  # noqa: E402
from . import signal  # noqa: E402
from . import geometric  # noqa: E402
from . import _C_ops  # noqa: E402  (kernel-level op surface, reference paddle._C_ops)
from . import regularizer  # noqa: E402
from . import sysconfig  # noqa: E402
from . import reader  # noqa: E402
from . import hub  # noqa: E402
from .reader import batch  # noqa: E402
from .hapi import callbacks  # noqa: E402


def in_dynamic_mode():
    """reference paddle.in_dynamic_mode — True outside static building."""
    from . import static as _static

    return not _static.in_static_mode()


def disable_signal_handler():
    """reference paddle.disable_signal_handler — the reference installs
    C++ signal handlers that can conflict with other runtimes; this build
    installs none, so there is nothing to disable (documented no-op)."""


class version:  # noqa: N801 — reference paddle.version module shape
    full_version = "0.4.0"
    major, minor, patch = "0", "4", "0"
    rc = "0"
    cuda_version = "False"
    cudnn_version = "False"
    xpu_version = "False"
    istaged = True
    commit = "tpu-native"

    @staticmethod
    def show():
        print(f"paddle_tpu {version.full_version} (tpu-native; XLA/PJRT)")

    @staticmethod
    def cuda():
        return "False"

    @staticmethod
    def cudnn():
        return "False"


__version__ = version.full_version


def _maybe_install_graftlint_runtime():
    """GRAFTLINT_RUNTIME=1 (raise) / =warn: enforce no-host-sync-under-trace
    at runtime via the sync-observer hook — the dynamic cross-check for the
    static GL001 rule (tools/graftlint, docs/LINTING.md)."""
    import os as _os

    # "0"/"false"/"off" must mean OFF (the conventional env idiom), not
    # "truthy string → strict raise mode"
    if _os.environ.get("GRAFTLINT_RUNTIME", "").strip().lower() in (
            "", "0", "false", "off", "no"):
        return
    try:
        from tools.graftlint import runtime as _glrt
    except ImportError:
        # installed without the repo's tools/ tree alongside — the static
        # linter is a dev-time tool, its absence must not break the package
        import warnings as _warnings

        _warnings.warn(
            "GRAFTLINT_RUNTIME is set but tools.graftlint is not importable; "
            "runtime host-sync checks disabled", RuntimeWarning)
        return
    _glrt.install_runtime_checks()


_maybe_install_graftlint_runtime()
