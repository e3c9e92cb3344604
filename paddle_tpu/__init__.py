"""paddle_tpu — a TPU-native deep learning framework with the API surface of
PaddlePaddle (reference: /root/reference, a Paddle v2.3 fork).

Not a port: compute lowers to XLA via jax/jnp/pallas; distribution is GSPMD
over jax.sharding meshes; eager mode is XLA-eager with a lightweight autograd
tape; the performance path compiles whole train steps with jax.jit.
"""
__version__ = "0.1.0"

from . import autograd, framework, tensor
from .framework import (  # noqa: F401
    CPUPlace,
    CUDAPlace,
    Parameter,
    Tensor,
    TPUPlace,
    bfloat16,
    bool,  # noqa: A004
    complex64,
    complex128,
    disable_static,
    dtype,
    enable_grad,
    float16,
    float32,
    float64,
    get_default_dtype,
    get_device,
    get_rng_state,
    in_dynamic_mode,
    int8,
    int16,
    int32,
    int64,
    is_grad_enabled,
    no_grad,
    seed,
    set_default_dtype,
    set_device,
    set_rng_state,
    uint8,
)
from .framework import (  # noqa: F401
    create_parameter,
    enable_static,
    in_dygraph_mode,
    set_grad_enabled,
    set_printoptions,
)
from .framework.device import CUDAPinnedPlace, NPUPlace  # noqa: F401
from .framework.random import get_rng_state as get_cuda_rng_state  # noqa: F401
from .framework.random import set_rng_state as set_cuda_rng_state  # noqa: F401
from .framework.core import to_tensor  # noqa: F401
from .tensor import *  # noqa: F401,F403
from .autograd import grad  # noqa: F401

# subpackages (gate lets the core be imported standalone during bring-up)
import os as _os

if _os.environ.get("PADDLE_TPU_CORE_ONLY") != "1":
    from . import nn  # noqa: F401,E402
    from . import optimizer  # noqa: F401,E402
    from . import distributed  # noqa: F401,E402
    from . import io  # noqa: F401,E402
    from . import metric  # noqa: F401,E402
    from . import amp  # noqa: F401,E402
    from . import vision  # noqa: F401,E402
    from . import jit  # noqa: F401,E402
    from . import static  # noqa: F401,E402
    from . import distribution  # noqa: F401,E402
    from . import incubate  # noqa: F401,E402
    from .hapi.model import Model  # noqa: F401,E402
    from .framework.io import load, save  # noqa: F401,E402
    from . import fft  # noqa: F401,E402
    from . import signal  # noqa: F401,E402
    from . import sparse  # noqa: F401,E402
    from . import device  # noqa: F401,E402
    from . import regularizer  # noqa: F401,E402
    from . import profiler  # noqa: F401,E402
    from . import linalg  # noqa: F401,E402
    from . import text  # noqa: F401,E402
    from . import hub  # noqa: F401,E402
    from . import debug  # noqa: F401,E402
    from . import models  # noqa: F401,E402
    from . import utils  # noqa: F401,E402
    from .hapi import callbacks  # noqa: F401,E402
    from . import compat  # noqa: F401,E402
    from . import cost_model  # noqa: F401,E402
    from . import dataset  # noqa: F401,E402
    from . import reader  # noqa: F401,E402
    from . import sysconfig  # noqa: F401,E402
    from . import inference  # noqa: F401,E402
    from . import onnx  # noqa: F401,E402
    from . import autograd as _autograd_ns  # noqa: F401,E402
    from .device import (  # noqa: F401,E402
        CustomPlace,
        IPUPlace,
        MLUPlace,
        XPUPlace,
        get_cudnn_version,
        is_compiled_with_cinn,
        is_compiled_with_cuda,
        is_compiled_with_ipu,
        is_compiled_with_mlu,
        is_compiled_with_npu,
        is_compiled_with_rocm,
        is_compiled_with_tpu,
        is_compiled_with_xpu,
    )
    from .nn.layer_base import ParamAttr  # noqa: F401,E402
    from .distributed.parallel import DataParallel  # noqa: F401,E402

    flatten = tensor.manipulation.flatten  # keep function (not module) at top level


def monkey_patch_math_varbase():
    """Tensor operators are patched at import (reference patches lazily)."""
    return None


def monkey_patch_variable():
    return None


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Count the forward FLOPs of a model (reference python/paddle/hapi/
    dynamic_flops.py). Uses jax's cost analysis on the traced forward — the
    XLA-native answer rather than per-layer hooks."""
    import numpy as _np
    import jax as _jax
    from .framework.core import Tensor as _T

    x = _np.zeros(input_size, _np.float32)

    def fwd(v):
        out = net(_T(v))
        return out._value if isinstance(out, _T) else out

    try:
        lowered = _jax.jit(fwd).lower(x)
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        total = int(cost.get("flops", 0))
    except Exception:
        total = 0
    if print_detail:
        print(f"Total FLOPs: {total}")
    return total


def batch(reader, batch_size, drop_last=False):
    """Legacy reader combinator (reference python/paddle/batch.py)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


def get_flags(flags):
    names = flags if isinstance(flags, (list, tuple)) else [flags]
    return {n: _FLAGS.get(n) for n in names}


def set_flags(flags):
    _FLAGS.update(flags)


_FLAGS = {}


def disable_signal_handler():
    pass


version = type("version", (), {"full_version": __version__,
                               "commit": "tpu-native", "istaged": True})


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.model_summary import summary as _summary
    return _summary(net, input_size, dtypes, input)
