"""Jitted methods that do not keep their instance alive.

``self._step = jax.jit(self._step_impl)`` builds a reference cycle: the
instance holds the jitted function, which holds the bound method, which
holds the instance.  A dropped engine or page allocator then keeps its
device buffers (weights, KV page pool) until Python's cycle collector
happens to run, so building engines one after another in one process runs
the device out of memory.  ``weak_method`` reaches the instance through a
weak reference instead: the last outside reference going away frees it and
its buffers at once.
"""
from __future__ import annotations

import inspect
import weakref


def weak_method(method):
    """A plain function calling bound ``method`` through a weak reference
    to its instance; ``jax.jit`` it like the method itself (it carries the
    method's name and signature, so ``static_argnames`` resolve)."""
    ref = weakref.WeakMethod(method)

    def call(*args, **kwargs):
        return ref()(*args, **kwargs)

    call.__name__ = method.__name__
    call.__qualname__ = method.__qualname__
    call.__signature__ = inspect.signature(method)
    return call
