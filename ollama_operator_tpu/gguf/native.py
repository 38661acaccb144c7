"""ctypes loader for the native dequant kernels (native/dequant.cpp).

Builds the shared library on first use (g++ -O3) into native/build/ and
patches the hot entries of gguf.dequant's dispatch table. Everything degrades
gracefully to the numpy reference path if no compiler is available.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from . import dequant as DQ
from . import reader as R

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "dequant.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")
_LIB = os.path.join(_BUILD_DIR, "libtpuop_dequant.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", _LIB, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return True
    except (subprocess.SubprocessError, FileNotFoundError):
        return False


def load() -> Optional[ctypes.CDLL]:
    """Build (if needed) and load the native library; None on failure."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_LIB) or (
                os.path.exists(_SRC)
                and os.path.getmtime(_SRC) > os.path.getmtime(_LIB)):
            if not os.path.exists(_SRC) or not _build():
                return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError:
            return None
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
        u16p = np.ctypeslib.ndpointer(np.uint16, flags="C_CONTIGUOUS")
        for name in ("dq_f16", "dq_bf16", "dq_q4_0", "dq_q8_0", "dq_q4_k",
                     "dq_q5_k", "dq_q6_k"):
            fn = getattr(lib, name)
            fn.argtypes = [u8p, f32p, ctypes.c_int64]
            fn.restype = None
        lib.f32_to_bf16.argtypes = [f32p, u16p, ctypes.c_int64]
        lib.f32_to_bf16.restype = None
        _lib = lib
        return _lib


def path_used() -> str:
    """Which dequant path this process took: "native", "numpy" (the
    library could not be built or loaded), or "none" (nothing has been
    dequantized yet, e.g. the transcode came from the cache)."""
    if not _tried:
        return "none"
    return "native" if _lib is not None else "numpy"


_NATIVE_MAP = {
    R.GGML_F16: "dq_f16",
    R.GGML_BF16: "dq_bf16",
    R.GGML_Q4_0: "dq_q4_0",
    R.GGML_Q8_0: "dq_q8_0",
    R.GGML_Q4_K: "dq_q4_k",
    R.GGML_Q5_K: "dq_q5_k",
    R.GGML_Q6_K: "dq_q6_k",
}


def native_dequantize(raw: np.ndarray, ggml_type: int) -> Optional[np.ndarray]:
    """Flat float32 output, or None if this type has no native kernel."""
    lib = load()
    if lib is None or ggml_type not in _NATIVE_MAP:
        return None
    fname = _NATIVE_MAP[ggml_type]
    be, bb = R.BLOCK_LAYOUT[ggml_type]
    raw = np.ascontiguousarray(raw)
    n_blocks = raw.nbytes // bb
    out = np.empty(n_blocks * be, np.float32)
    n_arg = raw.nbytes // 2 if be == 1 else n_blocks
    getattr(lib, fname)(raw, out, n_arg)
    return out


_installed = False


def install():
    """Patch gguf.dequant.dequantize to prefer the native path."""
    global _installed
    if _installed:
        return True
    if load() is None:
        return False
    _installed = True
    orig = DQ.dequantize

    def fast_dequantize(raw, ggml_type, shape):
        out = native_dequantize(raw, ggml_type)
        if out is not None:
            return out.reshape(shape)
        return orig(raw, ggml_type, shape)

    DQ.dequantize = fast_dequantize
    # dequantize_tensor resolves DQ.dequantize dynamically? It calls the
    # module-level name; rebinding the module attribute is enough only if it
    # looks it up at call time — patch it too for safety.
    def fast_tensor(f, t):
        return fast_dequantize(f.raw(t), t.ggml_type, t.shape)
    DQ.dequantize_tensor = fast_tensor
    return True
