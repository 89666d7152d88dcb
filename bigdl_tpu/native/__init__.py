"""Native C++ runtime components — the TPU-native counterpart of the
reference's BigDL-core JNI layer (SURVEY §2.1: ``mkl-java``/``bigdl-native``
consumed through ``com.intel.analytics.bigdl.mkl.MKL``, plus
``netty/Crc32c.java``).

On TPU the *device* hot path is XLA-compiled (MXU for gemm, VPU for
elementwise); what stays native here is exactly what stays native in the
reference's runtime:

- masked **CRC32C** for TFRecord/TensorBoard event framing
  (``visualization/tensorboard/RecordWriter.scala:30``),
- CPU **oracle kernels** (BLAS gemm/gemv/ger/axpy/dot/scal, VML
  elementwise, im2col/col2im, maxpool fwd/bwd — the reference's
  ``tensor/DenseTensorBLAS.scala`` + ``nn/NNPrimitive.scala`` hot loops)
  used as the host-side ground truth by the test suite,
- the **multi-threaded batch assembler** for the input pipeline
  (``dataset/image/MTLabeledBGRImgToBatch.scala``).

The shared library is compiled from ``src/*.cc`` with ``make`` on first use
(and again whenever a source or the Makefile is newer than the binary) and
bound via ctypes; every entry point has a pure-NumPy fallback so the
package works without a toolchain — :func:`is_native_loaded` says which
one a process ended up with.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libbigdl_native.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False
_disabled = False  # no_native seen -> short-circuit (hot paths)
_disabled_env: Optional[str] = None   # BIGDL_TPU_NO_NATIVE when latched
_disabled_cfg = None                  # installed config object when latched


def _try_load() -> Optional[ctypes.CDLL]:
    """Build (once) and load the native library; None if unavailable."""
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    global _disabled, _disabled_env, _disabled_cfg
    if _build_failed:
        return None
    from bigdl_tpu.utils import config as _cfgmod
    from bigdl_tpu.utils.config import get_config

    if _disabled:
        # latched while no_native was truthy; stay latched only while
        # BOTH knob sources (env var, installed config) are unchanged so
        # clearing either re-enables native like every other BIGDL_* knob
        if (os.environ.get("BIGDL_TPU_NO_NATIVE") == _disabled_env
                and _cfgmod._config is _disabled_cfg):
            return None
        _disabled = False
    if get_config().no_native:
        # cache the decision: _try_load sits on per-record hot paths
        # (crc32c framing), so don't re-resolve the config every call
        _disabled = True
        _disabled_env = os.environ.get("BIGDL_TPU_NO_NATIVE")
        _disabled_cfg = _cfgmod._config
        return None
    with _lock:
        if _lib is not None:
            return _lib
        # make decides every time (once per process): a binary older
        # than src/*.cc or the Makefile is rebuilt, so a stale .so left
        # on disk — git ignores it — never outlives its sources
        try:
            subprocess.run(["make", "-s"], cwd=_DIR, check=True,
                           capture_output=True, timeout=120)
        except (OSError, subprocess.SubprocessError):
            if not os.path.exists(_LIB_PATH):
                _build_failed = True
                return None
            # no toolchain here, but a binary someone built: use it
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            _build_failed = True
            return None
        # -- signatures --------------------------------------------------
        u8p = ctypes.POINTER(ctypes.c_uint8)
        f32p = ctypes.POINTER(ctypes.c_float)
        f64p = ctypes.POINTER(ctypes.c_double)
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.bigdl_crc32c.restype = ctypes.c_uint32
        lib.bigdl_crc32c.argtypes = [u8p, ctypes.c_size_t, ctypes.c_uint32]
        lib.bigdl_masked_crc32c.restype = ctypes.c_uint32
        lib.bigdl_masked_crc32c.argtypes = [u8p, ctypes.c_size_t]
        lib.bigdl_sgemm.argtypes = [
            ctypes.c_char, ctypes.c_char, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_float, f32p, ctypes.c_int, f32p,
            ctypes.c_int, ctypes.c_float, f32p, ctypes.c_int]
        lib.bigdl_dgemm.argtypes = [
            ctypes.c_char, ctypes.c_char, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_double, f64p, ctypes.c_int, f64p,
            ctypes.c_int, ctypes.c_double, f64p, ctypes.c_int]
        lib.bigdl_sgemv.argtypes = [
            ctypes.c_char, ctypes.c_int, ctypes.c_int, ctypes.c_float,
            f32p, ctypes.c_int, f32p, ctypes.c_int, ctypes.c_float, f32p,
            ctypes.c_int]
        lib.bigdl_sger.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_float, f32p, ctypes.c_int,
            f32p, ctypes.c_int, f32p, ctypes.c_int]
        lib.bigdl_saxpy.argtypes = [ctypes.c_int, ctypes.c_float, f32p,
                                    ctypes.c_int, f32p, ctypes.c_int]
        lib.bigdl_sdot.restype = ctypes.c_float
        lib.bigdl_sdot.argtypes = [ctypes.c_int, f32p, ctypes.c_int, f32p,
                                   ctypes.c_int]
        lib.bigdl_sscal.argtypes = [ctypes.c_int, ctypes.c_float, f32p,
                                    ctypes.c_int]
        for nm in ("Add", "Sub", "Mul", "Div"):
            getattr(lib, f"bigdl_vs{nm}").argtypes = [ctypes.c_int, f32p,
                                                      f32p, f32p]
        for nm in ("Ln", "Exp", "Sqrt", "Tanh", "Log1p", "Abs"):
            getattr(lib, f"bigdl_vs{nm}").argtypes = [ctypes.c_int, f32p, f32p]
        lib.bigdl_vsPowx.argtypes = [ctypes.c_int, f32p, ctypes.c_float, f32p]
        lib.bigdl_im2col.argtypes = [f32p] + [ctypes.c_int] * 9 + [f32p]
        lib.bigdl_col2im.argtypes = [f32p] + [ctypes.c_int] * 9 + [f32p]
        lib.bigdl_maxpool_fwd.argtypes = \
            [f32p] + [ctypes.c_int] * 9 + [f32p, i32p]
        lib.bigdl_maxpool_bwd.argtypes = \
            [f32p, i32p] + [ctypes.c_int] * 5 + [f32p]
        lib.bigdl_batch_crop_normalize.argtypes = [
            u8p] + [ctypes.c_int] * 6 + [i32p, i32p, u8p, f32p, f32p, f32p,
                                         ctypes.c_int]
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.bigdl_parse_examples.restype = ctypes.c_int64
        lib.bigdl_parse_examples.argtypes = [
            u8p, i64p, ctypes.c_int64, ctypes.POINTER(ctypes.c_char_p),
            i32p, i64p, ctypes.POINTER(u8p), ctypes.c_int32,
            ctypes.c_int32]
        _lib = lib
        return _lib


def is_native_loaded() -> bool:
    """Analogue of the reference's ``MKL.isMKLLoaded`` guard."""
    return _try_load() is not None


def _u8(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _f32(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _i32(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


# ---------------------------------------------------------------------------
# CRC32C
# ---------------------------------------------------------------------------
_CRC_TABLE = None


def _crc_table():
    global _CRC_TABLE
    if _CRC_TABLE is None:
        poly = 0x82F63B78
        tbl = np.zeros(256, np.uint32)
        for i in range(256):
            crc = i
            for _ in range(8):
                crc = (crc >> 1) ^ (poly if crc & 1 else 0)
            tbl[i] = crc
        _CRC_TABLE = tbl
    return _CRC_TABLE


def crc32c(data: bytes, crc: int = 0) -> int:
    lib = _try_load()
    buf = np.frombuffer(data, np.uint8)
    if lib is not None:
        return int(lib.bigdl_crc32c(_u8(buf), len(buf),
                                    ctypes.c_uint32(crc)))
    tbl = _crc_table()
    c = (~crc) & 0xFFFFFFFF
    for b in buf.tolist():
        c = int(tbl[(c ^ b) & 0xFF]) ^ (c >> 8)
    return (~c) & 0xFFFFFFFF


def masked_crc32c(data: bytes) -> int:
    """TFRecord masked CRC (``netty/Crc32c.java`` semantics)."""
    c = crc32c(data)
    return (((c >> 15) | (c << 17)) + 0xA282EAD8) & 0xFFFFFFFF


# ---------------------------------------------------------------------------
# Oracle BLAS / VML (float32; column-major gemm like the MKL interface)
# ---------------------------------------------------------------------------
def gemm(transa: str, transb: str, alpha, A: np.ndarray, B: np.ndarray,
         beta, C: np.ndarray) -> np.ndarray:
    """Column-major gemm on 2-D float32/float64 arrays stored Fortran-order.
    Mirrors ``tensor/DenseTensorBLAS.scala:70-112``."""
    m, n = C.shape
    k = A.shape[1] if transa.upper() == "N" else A.shape[0]
    lib = _try_load()
    dt = A.dtype
    if lib is not None and dt == np.float32:
        Af = np.asfortranarray(A, np.float32)
        Bf = np.asfortranarray(B, np.float32)
        Cf = np.asfortranarray(C, np.float32)
        lib.bigdl_sgemm(transa.encode()[:1], transb.encode()[:1], m, n, k,
                        np.float32(alpha), _f32(Af), Af.shape[0], _f32(Bf),
                        Bf.shape[0], np.float32(beta), _f32(Cf), Cf.shape[0])
        return np.ascontiguousarray(Cf)
    Aop = A.T if transa.upper() == "T" else A
    Bop = B.T if transb.upper() == "T" else B
    return (alpha * (Aop @ Bop) + beta * C).astype(dt)


def vml(op: str, a: np.ndarray, b=None) -> np.ndarray:
    """Elementwise oracle: op in Add/Sub/Mul/Div/Ln/Exp/Sqrt/Tanh/Log1p/
    Abs/Powx (b = scalar exponent for Powx)."""
    lib = _try_load()
    a = np.ascontiguousarray(a, np.float32)
    if lib is not None:
        y = np.empty_like(a)
        n = a.size
        if op in ("Add", "Sub", "Mul", "Div"):
            bb = np.ascontiguousarray(b, np.float32)
            getattr(lib, f"bigdl_vs{op}")(n, _f32(a), _f32(bb), _f32(y))
        elif op == "Powx":
            lib.bigdl_vsPowx(n, _f32(a), np.float32(b), _f32(y))
        else:
            getattr(lib, f"bigdl_vs{op}")(n, _f32(a), _f32(y))
        return y
    fns = {"Add": np.add, "Sub": np.subtract, "Mul": np.multiply,
           "Div": np.divide, "Ln": np.log, "Exp": np.exp, "Sqrt": np.sqrt,
           "Tanh": np.tanh, "Log1p": np.log1p, "Abs": np.abs}
    if op == "Powx":
        return np.power(a, np.float32(b))
    return fns[op](a, b) if b is not None and op in ("Add", "Sub", "Mul",
                                                     "Div") else fns[op](a)


# ---------------------------------------------------------------------------
# NN primitives (oracle for conv/pool tests)
# ---------------------------------------------------------------------------
def im2col(img: np.ndarray, kh, kw, sh, sw, ph, pw) -> np.ndarray:
    c, h, w = img.shape
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    lib = _try_load()
    img = np.ascontiguousarray(img, np.float32)
    if lib is not None:
        cols = np.empty((c * kh * kw, oh * ow), np.float32)
        lib.bigdl_im2col(_f32(img), c, h, w, kh, kw, sh, sw, ph, pw,
                         _f32(cols))
        return cols
    padded = np.pad(img, ((0, 0), (ph, ph), (pw, pw)))
    cols = np.empty((c * kh * kw, oh * ow), np.float32)
    for idx in range(c * kh * kw):
        j, i, ci = idx % kw, (idx // kw) % kh, idx // (kh * kw)
        patch = padded[ci, i:i + oh * sh:sh, j:j + ow * sw:sw]
        cols[idx] = patch.reshape(-1)
    return cols


def maxpool_fwd(x: np.ndarray, kh, kw, sh, sw, ph, pw):
    c, h, w = x.shape
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    lib = _try_load()
    x = np.ascontiguousarray(x, np.float32)
    if lib is not None:
        out = np.empty((c, oh, ow), np.float32)
        idx = np.empty((c, oh, ow), np.int32)
        lib.bigdl_maxpool_fwd(_f32(x), c, h, w, kh, kw, sh, sw, ph, pw,
                              _f32(out), _i32(idx))
        return out, idx
    out = np.full((c, oh, ow), -np.inf, np.float32)
    idx = np.full((c, oh, ow), -1, np.int32)
    for ci in range(c):
        for y in range(oh):
            for xx in range(ow):
                for i in range(kh):
                    ih = y * sh - ph + i
                    if not 0 <= ih < h:
                        continue
                    for j in range(kw):
                        iw = xx * sw - pw + j
                        if 0 <= iw < w and x[ci, ih, iw] > out[ci, y, xx]:
                            out[ci, y, xx] = x[ci, ih, iw]
                            idx[ci, y, xx] = ih * w + iw
    return out, idx


# ---------------------------------------------------------------------------
# Multithreaded batch assembly (native data-loader hot loop)
# ---------------------------------------------------------------------------
def batch_crop_normalize(imgs: np.ndarray, crop_h: int, crop_w: int,
                         oy: np.ndarray, ox: np.ndarray, flip: np.ndarray,
                         mean, std, num_threads: int = 0) -> np.ndarray:
    """uint8 [N,H,W,C] -> float32 [N,C,crop_h,crop_w] with per-image crop
    offsets, horizontal flips, and channel normalization; multithreaded in
    C++ when available."""
    n, h, w, c = imgs.shape
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    oy = np.ascontiguousarray(oy, np.int32)
    ox = np.ascontiguousarray(ox, np.int32)
    flip = np.ascontiguousarray(flip, np.uint8)
    lib = _try_load()
    if lib is not None and imgs.dtype == np.uint8:  # C++ kernel is uint8-only
        imgs = np.ascontiguousarray(imgs)
        out = np.empty((n, c, crop_h, crop_w), np.float32)
        lib.bigdl_batch_crop_normalize(
            _u8(imgs), n, h, w, c, crop_h, crop_w, _i32(oy), _i32(ox),
            _u8(flip), _f32(mean), _f32(std), _f32(out), num_threads)
        return out
    out = np.empty((n, c, crop_h, crop_w), np.float32)
    for i in range(n):
        patch = imgs[i, oy[i]:oy[i] + crop_h, ox[i]:ox[i] + crop_w, :]
        if flip[i]:
            patch = patch[:, ::-1, :]
        out[i] = ((patch.astype(np.float32) - mean) / std).transpose(2, 0, 1)
    return out


# ---------------------------------------------------------------------------
# Batch tf.Example parsing (native proto-wire walker)
# ---------------------------------------------------------------------------
def parse_examples_fixed(records, spec, num_threads: int = 0):
    """Parse serialized tf.Example records into dense arrays.

    ``spec``: list of ``(key, kind, count)`` where kind is ``"bytes"``
    (fixed-length raw payload -> uint8 [n, count]), ``"int64"``
    (-> int64 [n, count]) or ``"float"`` (-> float32 [n, count]).
    Returns one array per spec entry.  C++ multi-threaded when the
    native library is loaded; falls back to the Python wire walker
    (``dataset/tfrecord.parse_example``) otherwise.  Raises ValueError
    on a malformed record or a key/kind/size mismatch.
    """
    import ctypes

    kind_code = {"bytes": 0, "int64": 1, "float": 2}
    n = len(records)
    outs = []
    for key, kind, count in spec:
        if kind == "bytes":
            outs.append(np.empty((n, count), np.uint8))
        elif kind == "int64":
            outs.append(np.empty((n, count), np.int64))
        elif kind == "float":
            outs.append(np.empty((n, count), np.float32))
        else:
            raise ValueError(f"unknown kind {kind!r}")
    if n == 0:
        return outs

    lib = _try_load()
    if lib is not None:
        blob = b"".join(records)
        offsets = np.zeros(n + 1, np.int64)
        np.cumsum([len(r) for r in records], out=offsets[1:])
        blob_arr = np.frombuffer(blob, np.uint8)
        keys = (ctypes.c_char_p * len(spec))(
            *[k.encode() for k, _, _ in spec])
        kinds = np.asarray([kind_code[k] for _, k, _ in spec], np.int32)
        counts = np.asarray([c for _, _, c in spec], np.int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        out_ptrs = (u8p * len(spec))(
            *[o.ctypes.data_as(u8p) for o in outs])
        rc = lib.bigdl_parse_examples(
            _u8(blob_arr), offsets.ctypes.data_as(
                ctypes.POINTER(ctypes.c_int64)), n, keys, _i32(kinds),
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            out_ptrs, len(spec), num_threads)
        if rc != 0:
            raise ValueError(
                f"record {-int(rc) - 1} failed to parse (missing key, "
                f"wrong kind, or size mismatch)")
        return outs

    # pure-Python fallback: the reference walker, one record at a time
    from bigdl_tpu.dataset.tfrecord import parse_example

    for i, rec in enumerate(records):
        feats = parse_example(bytes(rec))
        for (key, kind, count), out in zip(spec, outs):
            if key not in feats:
                raise ValueError(f"record {i} failed to parse (missing "
                                 f"key {key!r})")
            v = feats[key]
            if kind == "bytes":
                if not isinstance(v, list) or len(v) != 1 \
                        or len(v[0]) != count:
                    raise ValueError(f"record {i} failed to parse "
                                     f"(bytes size mismatch for {key!r})")
                out[i] = np.frombuffer(v[0], np.uint8)
            else:
                arr = np.asarray(v).reshape(-1)
                if isinstance(v, list) or arr.size != count:
                    raise ValueError(f"record {i} failed to parse "
                                     f"(size/kind mismatch for {key!r})")
                out[i] = arr
    return outs
