"""Python 2/3 compatibility helpers (reference python/paddle/compat.py).

Kept for API parity: v2.1-era user code imports these for text/bytes
normalization and py2-style arithmetic.  Implementations are py3-native.
"""
from __future__ import annotations

import math as _math

__all__ = []

int_type = int
long_type = int


def _convert(obj, conv, inplace):
    if obj is None:
        return obj
    if isinstance(obj, list):
        if inplace:
            obj[:] = [_convert(o, conv, False) for o in obj]
            return obj
        return [_convert(o, conv, False) for o in obj]
    if isinstance(obj, set):
        vals = {_convert(o, conv, False) for o in obj}
        if inplace:
            obj.clear()
            obj.update(vals)
            return obj
        return vals
    return conv(obj)


def to_text(obj, encoding="utf-8", inplace=False):
    """bytes (or containers of bytes) → str; str passes through."""
    def conv(o):
        return o.decode(encoding) if isinstance(o, bytes) else str(o)

    return _convert(obj, conv, inplace)


def to_bytes(obj, encoding="utf-8", inplace=False):
    """str (or containers of str) → bytes; bytes passes through."""
    def conv(o):
        return o.encode(encoding) if isinstance(o, str) else bytes(o)

    return _convert(obj, conv, inplace)


def round(x, d=0):  # noqa: A001 - parity name
    """Py2-style half-away-from-zero rounding (py3 rounds half-to-even)."""
    p = 10 ** d
    if x > 0:
        return float(_math.floor((x * p) + 0.5)) / p
    if x < 0:
        return float(_math.ceil((x * p) - 0.5)) / p
    return 0.0


def floor_division(x, y):
    """Py2 ``/`` on ints == py3 ``//``."""
    return x // y


def get_exception_message(exc):
    """The message string of an exception object."""
    return str(exc)
