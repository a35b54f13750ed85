"""Native runtime bindings: build + load the C++ core via ctypes."""
import ctypes
import hashlib
import os
import subprocess

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "cpp", "runtime_core.cpp")
# build/ is in .gitignore: the library is built from the committed
# source on first use, never shipped as a binary
_BUILD = os.path.join(_HERE, "build")

_lib = None


def _so_path():
    """The library for THIS source: keyed by a hash of the source, not
    by mtimes (a fresh copy of the tree has arbitrary mtimes)."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD, f"libpaddle_tpu_runtime-{digest}.so")


def _build(so):
    os.makedirs(_BUILD, exist_ok=True)
    # build beside the target and rename: concurrent first uses (test
    # workers) never load a half-written library
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", tmp]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, so)


def get_lib():
    """Load (building on first use) the native runtime; None if no
    toolchain is available (pure-python fallbacks take over)."""
    global _lib
    if _lib is not None:
        return _lib
    try:
        so = _so_path()
        if not os.path.exists(so):
            _build(so)
        lib = ctypes.CDLL(so)
        lib.rb_create.restype = ctypes.c_void_p
        lib.rb_create.argtypes = [ctypes.c_size_t]
        lib.rb_push.restype = ctypes.c_int
        lib.rb_push.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                ctypes.c_int]
        lib.rb_pop.restype = ctypes.c_int
        lib.rb_pop.argtypes = [ctypes.c_void_p,
                               ctypes.POINTER(ctypes.c_uint64),
                               ctypes.c_int]
        lib.rb_close.argtypes = [ctypes.c_void_p]
        lib.rb_size.restype = ctypes.c_size_t
        lib.rb_size.argtypes = [ctypes.c_void_p]
        lib.rb_destroy.argtypes = [ctypes.c_void_p]
        lib.fast_stack.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_size_t,
            ctypes.c_size_t, ctypes.c_void_p, ctypes.c_int]
        lib.parallel_copy.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_size_t, ctypes.c_int]
        lib.ms_create.restype = ctypes.c_void_p
        lib.ms_create.argtypes = [ctypes.c_int,
                                  ctypes.POINTER(ctypes.c_int)]
        lib.ms_load_file.restype = ctypes.c_int64
        lib.ms_load_file.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int]
        lib.ms_shuffle.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.ms_num_records.restype = ctypes.c_uint64
        lib.ms_num_records.argtypes = [ctypes.c_void_p]
        lib.ms_batch_lens.restype = ctypes.c_uint64
        lib.ms_batch_lens.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.ms_fill_batch_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float)]
        lib.ms_fill_batch_i64.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64)]
        lib.ms_release.argtypes = [ctypes.c_void_p]
        lib.ms_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
    except Exception:
        _lib = None
    return _lib


from . import prefetch  # noqa: E402
from .prefetch import fast_collate_numpy  # noqa: E402
