"""paddle.inference — the serving API.

Reference: paddle/fluid/inference/ (90 k LoC AnalysisPredictor with IR passes,
TensorRT/ONNX sub-engines) + python wrappers python/paddle/inference/.

TPU-native collapse: a saved model is a serialized StableHLO program
(jit.save) — deserialization + XLA compilation replaces the analysis/pass
pipeline, and the TPU is the only execution provider. The Predictor keeps the
reference's handle-based API (get_input_names/get_input_handle/run) so
serving scripts port unchanged.
"""

from __future__ import annotations

import numpy as np

from ..framework.core import Tensor
from .. import jit as _jit

__all__ = ["Config", "Predictor", "create_predictor", "PlaceType", "DataType",
           "PagedServingEngine", "GenerationRequest"]


class PlaceType:
    CPU = "cpu"
    GPU = "tpu"  # scripts selecting "GPU" get the accelerator
    TPU = "tpu"


class DataType:
    FLOAT32 = "float32"
    INT64 = "int64"
    INT32 = "int32"


class Config:
    """reference: paddle.inference.Config (analysis config). Only the model
    path plumbing is meaningful on TPU; enable_* toggles are accepted no-ops
    (XLA always compiles/fuses)."""

    def __init__(self, prog_file=None, params_file=None):
        if prog_file is not None and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[: -len(".pdmodel")]
        self._prefix = prog_file
        self._flags = {}

    def set_prog_file(self, path):
        self._prefix = path[: -len(".pdmodel")] if path.endswith(".pdmodel") else path

    def prog_file(self):
        return (self._prefix or "") + ".pdmodel"

    def _noop(self, knob):
        # parity shims must not be SILENT no-ops (they mask user error):
        # one debug line per knob, once
        if knob not in self._flags:
            self._flags[knob] = True
            import logging

            logging.getLogger(__name__).info(
                "inference.Config.%s is a no-op on TPU: device placement, "
                "memory planning and graph optimization are owned by "
                "XLA/PJRT", knob)

    def enable_use_gpu(self, *a, **kw):
        self._noop("enable_use_gpu")

    def enable_memory_optim(self, *a, **kw):
        self._noop("enable_memory_optim")

    def switch_ir_optim(self, *a, **kw):
        self._noop("switch_ir_optim")

    def disable_glog_info(self):
        pass


class _Handle:
    def __init__(self):
        self._data = None

    def copy_from_cpu(self, arr):
        self._data = np.asarray(arr)

    def copy_to_cpu(self):
        return self._data

    def reshape(self, shape):
        if self._data is not None:
            self._data = self._data.reshape(shape)

    def share_external_data(self, arr):
        self.copy_from_cpu(arr)


class Predictor:
    """reference: paddle.inference.Predictor (AnalysisPredictor binding)."""

    def __init__(self, config: Config):
        self._layer = _jit.load(config._prefix)
        if not isinstance(self._layer, _jit.TranslatedLayer):
            raise ValueError(
                f"no saved program at {config.prog_file()}; jit.save with "
                "input_spec produces one")
        n_in = len(self._layer._exported.in_avals)
        self._in_names = [f"x{i}" for i in range(n_in)]
        self._inputs = {n: _Handle() for n in self._in_names}
        self._out_names = []
        self._outputs = {}

    def get_input_names(self):
        return list(self._in_names)

    def get_input_handle(self, name):
        return self._inputs[name]

    def run(self, inputs=None):
        """Either pass a list of ndarrays, or pre-fill input handles."""
        if inputs is None:
            inputs = [self._inputs[n].copy_to_cpu() for n in self._in_names]
        outs = self._layer(*inputs)
        if isinstance(outs, Tensor):
            outs = [outs]
        outs = [o.numpy() if isinstance(o, Tensor) else np.asarray(o) for o in outs]
        self._out_names = [f"out{i}" for i in range(len(outs))]
        self._outputs = {}
        for n, o in zip(self._out_names, outs):
            h = _Handle()
            h.copy_from_cpu(o)
            self._outputs[n] = h
        return outs

    def get_output_names(self):
        return list(self._out_names)

    def get_output_handle(self, name):
        return self._outputs[name]


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)

from . import serving  # noqa: E402
from .serving import GenerationRequest  # noqa: E402
from .paged import PagedServingEngine  # noqa: E402
