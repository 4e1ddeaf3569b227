"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capability surface, built on JAX/XLA/Pallas/pjit.

Layer map (TPU-native; see SURVEY.md for the reference's):
  core/        L0-L2: Place, dtype, flags, RNG, Tensor (PJRT buffers),
               dispatch (per-op XLA compile cache) + tape autograd engine
  ops/         L3: pure-jax kernels (the PHI-kernel analogue; Pallas in ops/pallas)
  tensor_api   L9: the ~500-function paddle.* tensor API
  nn/          Layer system, functional ops, initializers
  optimizer/   optimizers + lr schedulers (eager step() and pure update core)
  amp/         bf16 auto_cast O1/O2 + GradScaler
  jit/         to_static: whole-program jax.jit tracing (the executor zoo)
  static/      Program/Executor compatibility facade
  io/          Dataset/DataLoader
  distributed/ fleet, collectives over jax.sharding.Mesh, launch
  parallel/    mesh topology, TP/PP/EP/SP engines, sharding (ZeRO)
  vision/ hapi/ metric/ ...  user-facing packages
"""
from __future__ import annotations

from . import version  # noqa: F401
from .version import full_version as __version__  # noqa: F401


def __getattr__(name):
    # lazy: version.commit costs a git subprocess on first access
    if name == "__git_commit__":
        return version.commit
    raise AttributeError(name)

import jax as _jax

# paddle semantics: float64 tensors and int64 default integer dtype are
# first-class (reference exposes full fp64 kernels); jax disables x64 by
# default, so enable it once at import. TPU compute paths use f32/bf16
# explicitly, so this does not affect accelerator performance.
_jax.config.update("jax_enable_x64", True)

# persistent compile cache, placed from outside: JAX_COMPILATION_CACHE_DIR
# wins (jax reads it itself; no directory is set in code then). Otherwise a
# FIXED path in the checkout — the path is part of the cache key, so a
# directory that moves (tempfile, pid, time) would never hit.
import os as _os

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache"),
    )

from . import core
from .core import (  # noqa: F401
    CPUPlace,
    CUDAPinnedPlace,
    DType,
    Generator,
    Place,
    TPUPlace,
    Tensor,
    bfloat16,
    bool_,
    complex64,
    complex128,
    device_count,
    enable_grad,
    float16,
    float32,
    float64,
    get_default_dtype,
    get_device,
    get_rng_state,
    int8,
    int16,
    int32,
    int64,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    no_grad,
    seed,
    set_default_dtype,
    set_device,
    set_grad_enabled,
    set_rng_state,
    to_tensor,
    uint8,
)
from .core.flags import get_flags, set_flags  # noqa: F401

# the full tensor function API (paddle.add, paddle.matmul, ...)
from .tensor_api import *  # noqa: F401,F403
from . import tensor_api as _tensor_api

# subpackages — imported when present (built up milestone by milestone; the
# list mirrors the reference's python/paddle/ package tree)
import importlib as _importlib

for _pkg in (
    "analysis",
    "nn",
    "regularizer",
    "sysconfig",
    "callbacks",
    "optimizer",
    "autograd",
    "amp",
    "io",
    "jit",
    "static",
    "linalg",
    "metric",
    "vision",
    "framework",
    "distributed",
    "incubate",
    "profiler",
    "resilience",
    "hapi",
    "text",
    "distribution",
    "sparse",
    "fft",
    "signal",
    "onnx",
    "inference",
    "serving",
    "device",
    "hub",
    "utils",
    "cost_model",
    "quantization",
    "reader",
    "compat",
    "dataset",
):
    try:
        globals()[_pkg] = _importlib.import_module(f".{_pkg}", __name__)
    except ModuleNotFoundError as _e:
        if f"paddle_tpu.{_pkg}" not in str(_e):
            raise  # real import error inside an existing subpackage

from .batch import batch  # noqa: E402,F401

if "autograd" in globals() and hasattr(globals()["autograd"], "grad"):
    grad = globals()["autograd"].grad
if "framework" in globals() and hasattr(globals()["framework"], "io_utils"):
    load = globals()["framework"].io_utils.load
    save = globals()["framework"].io_utils.save
if "hapi" in globals():
    Model = globals()["hapi"].Model
    summary = globals()["hapi"].summary
if "distributed" in globals() and hasattr(globals()["distributed"], "parallel"):
    DataParallel = globals()["distributed"].parallel.DataParallel
if "static" in globals():
    disable_static = globals()["static"].disable_static
    enable_static = globals()["static"].enable_static

in_dynamic_mode = _tensor_api.in_dynamic_mode


def is_grad_enabled():
    return core.is_grad_enabled()


# remaining top-level reference names (python/paddle/__init__.py __all__)
from .core.place import (  # noqa: E402,F401
    CUDAPlace,
    CustomPlace,
    IPUPlace,
    MLUPlace,
    NPUPlace,
    XPUPlace,
)

bool = bool_  # noqa: A001 — paddle.bool is the dtype (reference parity)
dtype = DType
if "nn" in globals():
    ParamAttr = globals()["nn"].ParamAttr
if "hapi" in globals():
    from .hapi.dynamic_flops import flops  # noqa: E402,F401

# the accelerator generator state IS the cuda one on this build
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state
