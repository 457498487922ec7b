"""Loader for the native C++ shuffle runtime (native/vega_native.cpp).

Builds on demand with the in-tree Makefile if the shared object is missing
or older than its source (g++ is part of the toolchain); every caller has a
pure-Python fallback, so absence of a compiler degrades performance, not
correctness — and says so at WARNING with the compiler's output.

Named ops shared with the device tier's segment fast paths.
"""

from __future__ import annotations

import logging
import os
import subprocess
from vega_tpu.lint.sync_witness import named_lock

log = logging.getLogger("vega_tpu")

OP_ADD, OP_MIN, OP_MAX, OP_PROD = 0, 1, 2, 3
OP_BY_NAME = {"add": OP_ADD, "min": OP_MIN, "max": OP_MAX, "prod": OP_PROD}

_PY_OPS = {
    "add": lambda a, b: a + b,
    "min": min,
    "max": max,
    "prod": lambda a, b: a * b,
}


def decode_pairs_py(blob: bytes, is_int: bool):
    """Pure-Python decoder for the native 16-byte row frames (i64 key +
    i64/f64 payload) — keeps heterogeneous clusters correct when one side
    lacks the compiled module."""
    import struct

    fmt = "<qq" if is_int else "<qd"
    return [(k, v) for k, v in struct.iter_unpack(fmt, blob)]


def decode(blob: bytes, is_int: bool):
    """Decode a native row frame with the compiled module when present,
    else the pure-Python fallback (single source of the selection logic)."""
    nat = get()
    if nat is not None:
        return nat.decode_pairs(blob, is_int)
    return decode_pairs_py(blob, is_int)


def rows_from_columns(columns, nest: bool = False):
    """The row tuples of 1-D numpy columns, built by the compiled module:
    `list(zip(*[c.tolist() for c in columns]))`, or with `nest`
    `(c0, (c1, ...))` rows, equal in value and Python type, with no list
    a column in between and every tuple already untracked by the cyclic
    collector. None when the module is absent or a column's dtype is not
    a bool, integer, float32 or float64 one (strings, objects, dates): the
    caller then makes the rows itself."""
    nat = get()
    if nat is None:
        return None
    try:
        return nat.rows_from_columns(columns, nest)
    except TypeError:
        return None


def merge_encoded_py(flagged_blobs, op_name: str):
    """Pure-Python equivalent of _vega_native.merge_encoded."""
    op = _PY_OPS[op_name]
    combined: dict = {}
    for blob, is_int in flagged_blobs:
        for k, v in decode_pairs_py(blob, bool(is_int)):
            combined[k] = op(combined[k], v) if k in combined else v
    return list(combined.items())


class StreamingMerge:
    """Incremental reduce-side merge: feed encoded buckets AS THEY ARRIVE
    off the pipelined fetch (shuffle/fetcher.fetch_stream), so the merge
    overlaps network time instead of following the last byte.

    Backed by the C++ accumulator (merge_state_new/feed/finish) when the
    compiled module is present, else an exact pure-Python dict (bignum
    ints — no overflow case). finish() returns the merged pair list, or
    None iff the NATIVE path saw an int64 overflow: the caller must then
    redo the merge on the exact Python path (results must be bit-identical
    whichever host path ran — silently rounding through doubles is the one
    thing this contract forbids). Not thread-safe: one reduce task, one
    merger."""

    def __init__(self, op_name: str):
        self._op = OP_BY_NAME[op_name]
        nat = get()
        if nat is not None and hasattr(nat, "merge_state_new"):
            self._nat = nat
            self._state = nat.merge_state_new()
            self._py_op = None
            self._acc = None
        else:
            self._nat = None
            self._state = None
            self._py_op = _PY_OPS[op_name]
            self._acc = {}

    def feed(self, payload: bytes, is_int: bool) -> None:
        if self._nat is not None:
            self._nat.merge_state_feed(self._state, payload,
                                       1 if is_int else 0, self._op)
            return
        op = self._py_op
        acc = self._acc
        for k, v in decode_pairs_py(payload, bool(is_int)):
            acc[k] = op(acc[k], v) if k in acc else v

    def finish(self):
        if self._nat is not None:
            return self._nat.merge_state_finish(self._state)
        return list(self._acc.items())

_lock = named_lock("native._lock")
_native = None
_load_attempted = False


def _native_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native")


def _built_path() -> str:
    """Where native/Makefile puts the shared object (its OUT)."""
    import sysconfig

    return os.path.join(os.path.dirname(_native_dir()), "vega_tpu",
                        "_vega_native" + sysconfig.get_config_var(
                            "EXT_SUFFIX"))


def _stale() -> bool:
    """True when the shared object is absent or older than its source —
    a binary copied from another machine, or left behind by an older
    checkout, must not be what this one imports."""
    src = os.path.join(_native_dir(), "vega_native.cpp")
    if not os.path.isfile(src):
        return False  # no source to rebuild from: take what is there
    built = _built_path()
    return (not os.path.isfile(built)
            or os.path.getmtime(built) < os.path.getmtime(src))


def _try_build() -> bool:
    makefile_dir = _native_dir()
    if not os.path.isfile(os.path.join(makefile_dir, "Makefile")):
        return False
    try:
        subprocess.run(
            ["make", "-C", makefile_dir],
            check=True, capture_output=True, text=True, timeout=120,
        )
        return True
    except (subprocess.SubprocessError, OSError) as e:
        said = getattr(e, "stderr", None) or getattr(e, "stdout", None) or ""
        log.warning("native build failed (pure-Python fallback in use): "
                    "%s\n%s", e, said.strip())
        return False


def get():
    """Return the _vega_native module, or None if unavailable."""
    global _native, _load_attempted
    if _native is not None or _load_attempted:
        return _native
    with _lock:
        if _native is not None or _load_attempted:
            return _native
        # _load_attempted flips only AFTER the attempt concludes: setting
        # it up front let the lock-free fast path above observe
        # attempted=True with _native still None WHILE the import ran on
        # another thread — so the first tasks of a concurrent stage
        # nondeterministically fell back to the pickled path (a silent
        # perf loss the push plan's pre-merge accounting surfaced).
        # Callers racing the import now block on _lock and get the module.
        try:
            if not _stale() or _try_build():
                try:
                    from vega_tpu import _vega_native  # type: ignore[attr-defined]

                    _native = _vega_native
                except ImportError:
                    _native = None
        finally:
            # finally: a CORRUPT .so whose module init raises something
            # other than ImportError must still conclude the attempt —
            # later callers degrade to the pure-Python fallback instead of
            # re-raising on every hot-path call.
            _load_attempted = True
        if _native is not None:
            log.info("native shuffle runtime loaded")
    return _native
