"""Native (C++) host data pipeline.

Parity: reference paddle/fluid/framework/data_feed.cc + recordio/ +
async_executor feeding.  The on-device executor/allocator of the reference
has no TPU equivalent to build (XLA owns device execution and memory), so
the native layer is where it matters on TPU: the host input pipeline.  File
parsing, shuffle buffering and batch assembly run in C++ threads off the
GIL, overlapping the TPU step (see src/datafeed.cc).

The shared library is compiled on first use with g++ (no pip deps; bound via
ctypes) and is never committed: it is rebuilt whenever the sha256 of
`src/datafeed.cc` differs from the one stored beside it, so a binary that
travelled with a copy of the tree cannot outlive its source.  With no
compiler on the machine the pure-NumPy path in `fallback.py` provides
identical semantics; with a compiler, a failed build raises.
"""
import ctypes
import hashlib
import os
import shutil
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, 'src', 'datafeed.cc')
_LIB_PATH = os.path.join(_HERE, 'libptdatafeed.so')
_HASH_PATH = _LIB_PATH + '.sha256'
_lock = threading.Lock()
_lib = None
_no_compiler = False


def _src_hash():
    with open(_SRC, 'rb') as f:
        return hashlib.sha256(f.read()).hexdigest()


def _built_hash():
    try:
        with open(_HASH_PATH) as f:
            return f.read().strip()
    except OSError:
        return None


def _build(src_hash):
    # built beside the target and renamed into place: a process racing
    # this one never loads a half-written library
    tmp = '%s.tmp.%d' % (_LIB_PATH, os.getpid())
    cmd = ['g++', '-O2', '-shared', '-fPIC', '-std=c++14', '-pthread',
           _SRC, '-o', tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        raise RuntimeError('building %s failed (rc=%d):\n%s'
                           % (_LIB_PATH, e.returncode, e.stderr)) from e
    os.replace(tmp, _LIB_PATH)
    with open(_HASH_PATH, 'w') as f:
        f.write(src_hash + '\n')


def _bind(lib):
    i8p = ctypes.POINTER(ctypes.c_uint8)
    lib.ptrec_writer_open.restype = ctypes.c_void_p
    lib.ptrec_writer_open.argtypes = [ctypes.c_char_p]
    lib.ptrec_writer_write.restype = ctypes.c_int
    lib.ptrec_writer_write.argtypes = [
        ctypes.c_void_p, ctypes.c_int, i8p,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(i8p), ctypes.POINTER(ctypes.c_int64)]
    lib.ptrec_writer_close.argtypes = [ctypes.c_void_p]
    lib.ptrec_reader_open.restype = ctypes.c_void_p
    lib.ptrec_reader_open.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int64]
    lib.ptrec_reader_next.restype = ctypes.c_int
    lib.ptrec_reader_next.argtypes = [ctypes.c_void_p]
    lib.ptrec_reader_field_dtype.restype = ctypes.c_int
    lib.ptrec_reader_field_dtype.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ptrec_reader_field_ndim.restype = ctypes.c_int
    lib.ptrec_reader_field_ndim.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ptrec_reader_field_dims.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int64)]
    lib.ptrec_reader_field_data.restype = i8p
    lib.ptrec_reader_field_data.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ptrec_reader_error.restype = ctypes.c_char_p
    lib.ptrec_reader_error.argtypes = [ctypes.c_void_p]
    lib.ptrec_reader_close.argtypes = [ctypes.c_void_p]
    return lib


def get_lib():
    """Load (building if needed) the native library.  None only when the
    machine has no g++; a failed build or load raises."""
    global _lib, _no_compiler
    with _lock:
        if _lib is not None or _no_compiler:
            return _lib
        src_hash = _src_hash()
        if not os.path.exists(_LIB_PATH) or _built_hash() != src_hash:
            if shutil.which('g++') is None:
                _no_compiler = True
                return None
            _build(src_hash)
        _lib = _bind(ctypes.CDLL(_LIB_PATH))
        return _lib


def native_available():
    return get_lib() is not None


from .datafeed import (RecordWriter, RecordReader, BatchReader,  # noqa: E402
                       write_records, DataFeedDesc)

__all__ = ['get_lib', 'native_available', 'RecordWriter', 'RecordReader',
           'BatchReader', 'write_records', 'DataFeedDesc']
