"""paddle_tpu — a TPU-native deep learning framework.

A ground-up JAX/XLA/Pallas re-design with the capability surface of
PaddlePaddle (reference mounted at /root/reference; see SURVEY.md for the
layer map). The eager API feels like paddle dygraph; the performance path is
one jitted XLA step (paddle_tpu.jit), parallelism is mesh + GSPMD/shard_map
(paddle_tpu.distributed), and hot kernels are Pallas (paddle_tpu.ops.pallas).
"""
__version__ = "0.1.0"

from . import amp  # noqa: F401
from . import autograd  # noqa: F401
from . import jit  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import ops  # noqa: F401
from .core import random as _random_mod  # noqa: F401
from .core.random import get_rng_state, seed, set_rng_state  # noqa: F401
from .core.tape import enable_grad, no_grad, set_grad_enabled  # noqa: F401
from .core.tensor import Parameter, Tensor, to_tensor  # noqa: F401
from .core import dtype as _dtype_mod
from .core.dtype import (  # noqa: F401
    bfloat16, bool_, complex64, complex128, float16, float32, float64, int8,
    int16, int32, int64, uint8,
)
from .ops.registry import OPS as _OPS
from .ops.registry import install_method_tail as _install_mt
from .ops.registry import install_tensor_methods as _install_tm

# second pass: nn.functional etc. registered more ops (relu, softmax, …)
# after paddle_tpu.ops ran its install — pick up their method/inplace
# variants too (idempotent)
_install_tm()
_install_mt()

# re-export every registered op at top level (paddle.* flat namespace parity)
_g = globals()
for _name, _op in _OPS.items():
    _g.setdefault(_name, _op)
del _g


def __getattr__(name):
    # ops registered after import (e.g. distributed extensions)
    if name in _OPS:
        return _OPS[name]
    if name == "distributed":  # canonical home is paddle_tpu.parallel
        import importlib
        mod = importlib.import_module(".parallel", __name__)
        globals()[name] = mod
        return mod
    if name in ("parallel", "io", "hapi", "metric", "profiler", "vision",
                "models", "utils", "incubate", "static", "device", "runtime",
                "inference", "sparse", "text", "audio", "geometric",
                "quantization", "distribution", "fft", "signal",
                "regularizer", "linalg", "onnx", "callbacks", "hub",
                "sysconfig", "reader", "cost_model", "telemetry",
                "reliability"):
        import importlib
        try:
            mod = importlib.import_module(f".{name}", __name__)
        except ImportError as e:  # keep hasattr() working for probes
            raise AttributeError(
                f"module 'paddle_tpu' has no attribute {name!r}") from e
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def Model(*args, **kwargs):
    from .hapi.model import Model as _M
    return _M(*args, **kwargs)


def DataParallel(*args, **kwargs):
    from .parallel.api import DataParallel as _DP
    return _DP(*args, **kwargs)


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    return True


def get_default_dtype():
    return _dtype_mod.float32


_default_dtype = [_dtype_mod.float32]


def set_default_dtype(d):
    _default_dtype[0] = _dtype_mod.convert_dtype(d)


def disable_static(place=None):
    """paddle.disable_static parity: leave global static-graph mode."""
    from .static import graph as _g
    _g.disable_static_mode()


def enable_static():
    """paddle.enable_static parity: ops on static.data Variables record
    into default_main_program (reference: paddle/fluid/framework.py
    _dygraph_guard off). Eager Tensors keep working — recording only
    triggers on symbolic Variables, so the trace-based eager path and the
    recorded static path coexist."""
    from .static import graph as _g
    _g.enable_static_mode()


def in_dynamic_mode():
    from .static import graph as _g
    return not _g.in_static_mode()


def grad(*args, **kwargs):
    return autograd.grad(*args, **kwargs)


def device_count():
    import jax
    return jax.device_count()


def set_device(device):
    from .device import set_device as _set_device
    return _set_device(device)


def get_device():
    import jax
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def synchronize():
    import jax
    (jax.device_put(0) + 0).block_until_ready()


def save(obj, path, **kwargs):
    from .io.save_load import save as _save
    return _save(obj, path, **kwargs)


def load(path, **kwargs):
    from .io.save_load import load as _load
    return _load(path, **kwargs)


def summary(net, input_size=None, dtypes=None):
    from .hapi.summary import summary as _summary
    return _summary(net, input_size, dtypes)


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.summary import flops as _flops
    return _flops(net, input_size)


# ------------------------------------------------ top-level parity tail
# (reference python/paddle/__init__.py __all__)

dtype = _dtype_mod.DType if hasattr(_dtype_mod, "DType") else str
bool = _dtype_mod.bool_          # noqa: A001 — paddle.bool dtype alias


def iinfo(dt):
    import numpy as _np
    return _np.iinfo(_dtype_mod.convert_dtype(dt))


def finfo(dt):
    import numpy as _np
    return _np.finfo(_dtype_mod.convert_dtype(dt))


def is_tensor(x):
    return isinstance(x, Tensor)


def is_complex(x):
    import jax.numpy as _jnp
    d = x.dtype if hasattr(x, "dtype") else x
    return _jnp.issubdtype(_dtype_mod.convert_dtype(d), _jnp.complexfloating)


def is_integer(x):
    import jax.numpy as _jnp
    d = x.dtype if hasattr(x, "dtype") else x
    return _jnp.issubdtype(_dtype_mod.convert_dtype(d), _jnp.integer)


def is_floating_point(x):
    import jax.numpy as _jnp
    d = x.dtype if hasattr(x, "dtype") else x
    return _jnp.issubdtype(_dtype_mod.convert_dtype(d), _jnp.floating)


def rank(x):
    """paddle.rank: 0-d tensor holding ndim."""
    import jax.numpy as _jnp
    v = x._value if isinstance(x, Tensor) else x
    return to_tensor(_jnp.asarray(v.ndim, _jnp.int32))


def is_grad_enabled():
    from .core.tape import tape_enabled
    return tape_enabled()


def tolist(x):
    return (x.numpy() if isinstance(x, Tensor) else x).tolist()


def floor_mod(x, y):
    return _OPS["mod"](x, y)


def broadcast_shape(x_shape, y_shape):
    import numpy as _np
    return list(_np.broadcast_shapes(tuple(x_shape), tuple(y_shape)))


def get_cuda_rng_state():
    """CUDA-API-shaped alias over the TPU/global RNG state."""
    return [get_rng_state()]


def set_cuda_rng_state(state):
    set_rng_state(state[0] if isinstance(state, (list, tuple)) else state)


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def disable_signal_handler():
    pass  # reference installs fault handlers; nothing to disable here


class LazyGuard:
    """paddle.LazyGuard parity: in the reference this defers parameter
    materialization; initialization here is already cheap/deferred to
    first use, so the guard is a no-op context."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class CPUPlace:
    def __repr__(self):
        return "Place(cpu)"


class CUDAPlace:
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(gpu:{self.device_id})"


class CUDAPinnedPlace:
    def __repr__(self):
        return "Place(gpu_pinned)"


class NPUPlace(CUDAPlace):
    def __repr__(self):
        return f"Place(npu:{self.device_id})"


class TPUPlace:
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(tpu:{self.device_id})"


def ParamAttr(name=None, initializer=None, learning_rate=1.0,
              regularizer=None, trainable=True, do_model_average=True,
              need_clip=True):
    from .nn.param_attr import ParamAttr as _PA
    return _PA(name=name, initializer=initializer,
               learning_rate=learning_rate, regularizer=regularizer,
               trainable=trainable, do_model_average=do_model_average,
               need_clip=need_clip)


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """paddle.create_parameter parity (static+eager helper)."""
    from .nn import initializer as I
    init = default_initializer
    if attr is not None and getattr(attr, "initializer", None) is not None:
        init = attr.initializer
    if init is None:
        init = I.Constant(0.0) if is_bias else I.XavierNormal()
    p = Parameter(init(tuple(shape), _dtype_mod.convert_dtype(dtype)))
    if name:
        p.name = name
    return p


def batch(reader, batch_size, drop_last=False):
    """Legacy reader-decorator (reference python/paddle/batch.py)."""
    def gen():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return gen


def _tensor_method_alias(op, name):
    def f(x, *args, **kwargs):
        return _OPS[op](x, *args, **kwargs) if op in _OPS else \
            getattr(x, name)(*args, **kwargs)
    f.__name__ = name
    return f


def tanh_(x):
    return x.tanh_()


def scatter_(x, index, updates, overwrite=True):
    # Tensor method form snapshots the pre-mutation tape identity so the
    # recorded node's parent is the old value, not the rebound self
    return x.scatter_(index, updates, overwrite)


def reshape_(x, shape):
    return x.reshape_(shape)


def squeeze_(x, axis=None):
    return x.squeeze_(axis)


def unsqueeze_(x, axis):
    return x.unsqueeze_(axis)


def set_flags(flags):
    from .runtime import set_flags as _sf
    return _sf(flags)


def get_flags(names):
    from .runtime import get_flags as _gf
    return _gf(names)


def check_shape(x, shape):
    """Assert a tensor's shape (reference static check helper)."""
    import builtins
    got = list(x.shape)
    want = list(shape)
    # NB: bare `all` here would hit the re-exported paddle op
    ok = len(got) == len(want) and builtins.all(
        w in (-1, None) or g == w for g, w in zip(got, want))
    if not ok:
        raise ValueError(f"shape mismatch: got {got}, expected {want}")
    return x


def broadcast_tensors(inputs):
    """paddle.broadcast_tensors parity: broadcast all to a common shape."""
    import numpy as _np
    shapes = [tuple(t.shape) for t in inputs]
    target = _np.broadcast_shapes(*shapes)
    return [_OPS["broadcast_to"](t, list(target)) for t in inputs]


def index_add_(x, index, axis, value):
    return x.index_add_(index, axis, value)


def index_add(x, index, axis, value):
    return _OPS["index_add"](x, index, axis, value)
