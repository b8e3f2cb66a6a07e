"""ctypes bindings for the C++ TRec scanner (elasticdl_tpu/native/recordio.cc).

The native library is optional: readers fall back to the pure-Python codec in
elasticdl_tpu/data/record_format.py when the shared object has not been built
(`make -C elasticdl_tpu/native`). This mirrors the reference's split between
its Python PS and the Go/C++ fast path (SURVEY.md §2.4) — same format, same
semantics, faster scan.
"""

import ctypes
import os

from elasticdl_tpu.common.log_utils import default_logger as logger

_LIB = None
_TRIED = False


def _load():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "libtrecio.so")
    if not os.path.exists(path):
        # the .so is a build product (git-ignored): a fresh checkout
        # reads records with the Python codec until someone runs make
        logger.info("TRec codec: python (libtrecio.so not built)")
        return None
    try:
        lib = ctypes.CDLL(path)
        lib.trec_open.restype = ctypes.c_void_p
        lib.trec_open.argtypes = [ctypes.c_char_p]
        lib.trec_count.restype = ctypes.c_long
        lib.trec_count.argtypes = [ctypes.c_void_p]
        lib.trec_read.restype = ctypes.c_long
        lib.trec_read.argtypes = [
            ctypes.c_void_p,
            ctypes.c_long,
            ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.trec_free_buf.argtypes = [ctypes.c_char_p]
        lib.trec_close.argtypes = [ctypes.c_void_p]
        _LIB = lib
        logger.info("TRec codec: native (%s)", path)
    except OSError as e:
        logger.warning("TRec codec: python (%s failed to load: %s)",
                       path, e)
        _LIB = None
    return _LIB


def available():
    return _load() is not None


def record_count(path):
    lib = _load()
    h = lib.trec_open(path.encode())
    if not h:
        raise IOError("cannot open %s" % path)
    try:
        return int(lib.trec_count(h))
    finally:
        lib.trec_close(h)


def scan(path, start, count):
    """Yield `count` record payloads starting at record `start`."""
    lib = _load()
    h = lib.trec_open(path.encode())
    if not h:
        raise IOError("cannot open %s" % path)
    try:
        total = int(lib.trec_count(h))
        end = total if count < 0 else min(total, start + count)
        for i in range(start, end):
            buf = ctypes.c_char_p()
            n = lib.trec_read(h, i, ctypes.byref(buf))
            if n < 0:
                raise IOError("read error in %s at record %d" % (path, i))
            try:
                yield ctypes.string_at(buf, n)
            finally:
                lib.trec_free_buf(buf)
    finally:
        lib.trec_close(h)
