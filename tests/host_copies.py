"""Every host copy of a device value made while a block runs, and
whether ``columnar/batch.read_host`` made it.

jax copies a device array to the host through ``ArrayImpl._value``
(``int()``, ``bool()``, ``.tolist()``, ``jax.device_get``) or, for an
array on the CPU, through the buffer protocol (``np.asarray``,
``.item()``).  Both are hooked, on every thread."""

import contextlib
import sys
import traceback

from jax._src import array as jax_array


def _in_read_host(frame) -> bool:
    from spark_rapids_tpu.columnar import batch
    code = batch.read_host.__code__
    while frame is not None:
        if frame.f_code is code:
            return True
        frame = frame.f_back
    return False


@contextlib.contextmanager
def copies_outside_read_host():
    """Yields a list that gathers, for each copy made outside
    ``read_host`` while the block runs, the stack that made it."""
    cls = jax_array.ArrayImpl
    value, buffer = cls._value, cls.__buffer__
    outside = []

    def note():
        frame = sys._getframe(2)
        if not _in_read_host(frame):
            outside.append("".join(traceback.format_stack(frame, limit=8)))

    def hooked_value(self):
        note()
        return value.fget(self)

    def hooked_buffer(self, flags):
        note()
        return buffer(self, flags)

    cls._value, cls.__buffer__ = property(hooked_value), hooked_buffer
    try:
        yield outside
    finally:
        cls._value, cls.__buffer__ = value, buffer
