"""Reference python/paddle/incubate/multiprocessing/__init__.py: a
drop-in for the stdlib multiprocessing module with Tensor reducers
installed — `import paddle_tpu.incubate.multiprocessing as mp` then use
mp.Process / mp.Queue and put Tensors on them directly.

A chip belongs to one process: a parent that has touched JAX holds it,
and a child that asked for it would fail or hang. These children only
move host data (Tensors cross as shared-memory numpy buffers), so
`get_context`/`Process` here start them with JAX_PLATFORMS=cpu unless
the user pinned JAX_PLATFORMS themselves.
"""
import multiprocessing
import os

from multiprocessing import *  # noqa: F401,F403

from .reductions import init_reductions

__all__ = []
__all__ += multiprocessing.__all__


def _platform_env():
    """Env entries a child needs to stay off the parent's chip. Computed
    lazily at Process.start() time; a no-op when the user already pinned
    JAX_PLATFORMS (virtual device counts ride XLA_FLAGS in os.environ,
    which children inherit anyway)."""
    if os.environ.get("JAX_PLATFORMS"):
        return {}
    return {"JAX_PLATFORMS": "cpu"}


class _EnvInheritingProcess:
    """Mixin: set the platform env right before the interpreter for the
    child is launched, restore the parent's env after. Applies to both
    spawn (env captured at Popen time) and fork (inherited address
    space, env harmless)."""

    def start(self):
        injected = {k: v for k, v in _platform_env().items()
                    if k not in os.environ}
        for k, v in injected.items():
            os.environ[k] = v
        try:
            return super().start()
        finally:
            for k in injected:
                os.environ.pop(k, None)


# spawn pickles the Process object by CLASS REFERENCE, so every wrapped
# class must be a real module-level attribute here, not a per-call type()
_WRAPPED = {}
for _method in multiprocessing.get_all_start_methods():
    _base = multiprocessing.get_context(_method).Process
    _cls = type(_base.__name__, (_EnvInheritingProcess, _base),
                {"__module__": __name__})
    globals()[_base.__name__] = _cls
    _WRAPPED[_method] = _cls


class _EnvInheritingContext:
    """Proxy over a multiprocessing context whose Process class injects
    the platform env (everything else delegates). Pool is built with
    THIS proxy as its context so its workers also ride the wrapped
    Process — otherwise `mp.Pool` would bypass the env injection
    entirely."""

    def __init__(self, ctx):
        self._ctx = ctx
        self.Process = _WRAPPED[ctx.get_start_method()]

    def Pool(self, processes=None, initializer=None, initargs=(),
             maxtasksperchild=None):
        from multiprocessing.pool import Pool as _PoolCls
        return _PoolCls(processes, initializer, initargs,
                        maxtasksperchild, context=self)

    def __getattr__(self, name):
        return getattr(self._ctx, name)


def get_context(method=None):
    return _EnvInheritingContext(multiprocessing.get_context(method))


def Pool(processes=None, initializer=None, initargs=(),
         maxtasksperchild=None):
    return get_context().Pool(processes, initializer, initargs,
                              maxtasksperchild)


class Process(_EnvInheritingProcess, multiprocessing.Process):
    __module__ = __name__

init_reductions()
