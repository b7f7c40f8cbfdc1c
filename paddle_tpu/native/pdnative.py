"""The ``.pdnative`` deploy artifact: writer, reader, and the ctypes-backed
NativePredictor over the C++ PJRT runner (``csrc/pjrt_runner.cc``).

This is the native deployment story replacing the reference's C++ inference
stack (ref:paddle/fluid/inference/api/analysis_predictor.cc and the C API
ref:paddle/fluid/inference/capi_exp/pd_inference_api.h): one self-describing
binary file carrying StableHLO bytecode, serialized XLA compile options,
weights, and I/O specs. ``jit.save`` writes it next to ``.pdmodel`` when the
input spec is fully static; any C/C++ application linking
``libpaddle_tpu_native.so`` (or Python via :class:`NativePredictor`) can then
run the model on any PJRT plugin (``libtpu.so`` on TPU hosts) without
Python or jax at serve time.

Container layout (little-endian; reader in C++: pjrt_runner.cc load_artifact):

    magic "PDNATIVE" | u32 version=1 | u32 nsections
    section := u16 name_len | name | u64 data_len | data
    "args"    := u32 n | { u8 kind(0=weight,1=input) | u16 nlen | name |
                           u8 dtype | u8 ndim | i64 dims[] |
                           [weight: u64 nbytes | raw] }
    "outputs" := u32 n | { u16 nlen | name | u8 dtype | u8 ndim | i64 dims[] }

dtype codes are PJRT_Buffer_Type values so the C++ side passes them through.
"""
from __future__ import annotations

import ctypes
import os
import struct
import subprocess
from typing import List, Optional, Sequence

import numpy as np

MAGIC = b"PDNATIVE"
VERSION = 1

# PJRT_Buffer_Type values (third_party/pjrt_c_api.h)
_PJRT_TYPES = {
    "bool": 1, "int8": 2, "int16": 3, "int32": 4, "int64": 5,
    "uint8": 6, "uint16": 7, "uint32": 8, "uint64": 9,
    "float16": 10, "float32": 11, "float64": 12, "bfloat16": 13,
    "complex64": 14, "complex128": 15,
}
_PJRT_TYPES_INV = {v: k for k, v in _PJRT_TYPES.items()}


def _np_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def dtype_code(dt) -> int:
    name = np.dtype(dt).name if not hasattr(dt, "name") else dt.name
    try:
        return _PJRT_TYPES[str(name)]
    except KeyError:
        raise ValueError(f"dtype {name} has no PJRT buffer type") from None


class ArgSpec:
    """One exported-main argument (weight with data, or runtime input)."""

    def __init__(self, name: str, dtype, shape: Sequence[int],
                 data: Optional[bytes] = None):
        self.name = name
        self.dtype = np.dtype(dtype) if not isinstance(dtype, np.dtype) else dtype
        self.shape = tuple(int(d) for d in shape)
        self.data = data  # raw bytes => weight; None => runtime input

    @property
    def is_weight(self) -> bool:
        return self.data is not None


def _pack_name(name: str) -> bytes:
    b = name.encode()
    return struct.pack("<H", len(b)) + b


def _pack_spec(s: ArgSpec, with_kind: bool) -> bytes:
    out = b""
    if with_kind:
        out += struct.pack("<B", 0 if s.is_weight else 1)
    out += _pack_name(s.name)
    out += struct.pack("<BB", dtype_code(s.dtype), len(s.shape))
    out += struct.pack(f"<{len(s.shape)}q", *s.shape) if s.shape else b""
    if s.is_weight:
        out += struct.pack("<Q", len(s.data)) + s.data
    return out


def write(path: str, *, platform: str, compile_options: bytes,
          stablehlo: bytes, args: List[ArgSpec], outputs: List[ArgSpec]):
    """Serialize the deploy artifact to ``path``."""
    sections = [
        ("platform", platform.encode()),
        ("compile_options", compile_options),
        ("stablehlo", stablehlo),
        ("args", struct.pack("<I", len(args))
         + b"".join(_pack_spec(a, with_kind=True) for a in args)),
        ("outputs", struct.pack("<I", len(outputs))
         + b"".join(_pack_spec(o, with_kind=False) for o in outputs)),
    ]
    with open(path, "wb") as f:
        f.write(MAGIC + struct.pack("<II", VERSION, len(sections)))
        for name, data in sections:
            f.write(_pack_name(name) + struct.pack("<Q", len(data)) + data)


class _Cursor:
    def __init__(self, buf: bytes):
        self.buf, self.off = buf, 0

    def take(self, n: int) -> bytes:
        if self.off + n > len(self.buf):
            raise ValueError("truncated .pdnative")
        out = self.buf[self.off:self.off + n]
        self.off += n
        return out

    def unpack(self, fmt: str):
        return struct.unpack(fmt, self.take(struct.calcsize(fmt)))


def _read_spec(c: _Cursor, with_kind: bool) -> ArgSpec:
    is_weight = False
    if with_kind:
        (kind,) = c.unpack("<B")
        is_weight = kind == 0
    (nlen,) = c.unpack("<H")
    name = c.take(nlen).decode()
    dt, nd = c.unpack("<BB")
    dims = c.unpack(f"<{nd}q") if nd else ()
    data = None
    if is_weight:
        (nb,) = c.unpack("<Q")
        data = c.take(nb)
    return ArgSpec(name, _np_dtype(_PJRT_TYPES_INV[dt]), dims, data)


def read(path: str) -> dict:
    """Parse a .pdnative file (python-side mirror of the C++ loader, used by
    tests and tooling)."""
    with open(path, "rb") as f:
        buf = f.read()
    c = _Cursor(buf)
    if c.take(8) != MAGIC:
        raise ValueError("not a .pdnative file")
    version, nsec = c.unpack("<II")
    if version != VERSION:
        raise ValueError(f"unsupported .pdnative version {version}")
    out = {"args": [], "outputs": []}
    for _ in range(nsec):
        (nlen,) = c.unpack("<H")
        name = c.take(nlen).decode()
        (dlen,) = c.unpack("<Q")
        data = c.take(dlen)
        if name in ("platform",):
            out[name] = data.decode()
        elif name in ("compile_options", "stablehlo"):
            out[name] = data
        elif name == "args":
            sc = _Cursor(data)
            (n,) = sc.unpack("<I")
            out["args"] = [_read_spec(sc, True) for _ in range(n)]
        elif name == "outputs":
            sc = _Cursor(data)
            (n,) = sc.unpack("<I")
            out["outputs"] = [_read_spec(sc, False) for _ in range(n)]
    return out


def default_compile_options() -> bytes:
    """Serialized xla.CompileOptionsProto for 1-replica 1-partition inference,
    produced through jax's bundled xla_client (no proto dep of our own)."""
    from jax._src.lib import xla_client as xc

    opts = xc.CompileOptions()
    opts.num_replicas = 1
    opts.num_partitions = 1
    return opts.SerializeAsString()


# ------------------------------------------------------------ ctypes wrapper


def _lib():
    from . import load

    return load()  # pt_infer_* prototypes are declared in native._declare


def default_plugin_path() -> Optional[str]:
    """Best-effort discovery of a PJRT plugin .so on this host."""
    env = os.environ.get("PADDLE_TPU_PJRT_PLUGIN")
    if env:
        return env
    try:
        import libtpu

        return os.path.join(os.path.dirname(libtpu.__file__), "libtpu.so")
    except ImportError:
        return None


class NativePredictor:
    """Python handle over the C++ PJRT runner — the same code path a C/C++
    application gets by linking libpaddle_tpu_native.so directly."""

    def __init__(self, artifact_path: str, plugin_path: Optional[str] = None,
                 create_options: Optional[dict] = None):
        self._l = _lib()
        plugin = plugin_path or default_plugin_path()
        if plugin is None:
            raise RuntimeError(
                "no PJRT plugin found; set PADDLE_TPU_PJRT_PLUGIN")
        # create_options: plugin-specific PJRT_Client_Create NamedValues
        # ({str: str|int}). Serialized TYPE-TAGGED ("i:<int>" / "s:<str>")
        # into pt_infer_create_with_options — the Python type decides the
        # NamedValue type, so a digit-only STRING option (e.g. a numeric
        # session_id) stays kString, and no process-global env var is
        # mutated (thread-safe). libtpu needs none; a plugin that requires
        # NamedValues at create time gets them here. Pure-C users
        # without this entry point can export
        # PADDLE_TPU_PJRT_CREATE_OPTIONS instead (guess-typed).
        # None vs {} matters: an EXPLICIT empty dict means "no options,
        # period" — it goes through the with_options entry point with an
        # empty string, which the C++ side treats as zero NamedValues and,
        # unlike plain pt_infer_create, never falls back to the
        # PADDLE_TPU_PJRT_CREATE_OPTIONS env var.
        if create_options is not None:
            parts = []
            for k, v in create_options.items():
                if ";" in str(k) or "=" in str(k) or ";" in str(v):
                    raise ValueError(
                        f"create_options key/value may not contain ';' or "
                        f"'=': {k!r}={v!r}")
                # bools ride as ints (PJRT plugins read 0/1 Int64 knobs)
                tag = "i" if isinstance(v, (int, bool)) else "s"
                parts.append(f"{k}={tag}:{int(v) if tag == 'i' else v}")
            self._h = self._l.pt_infer_create_with_options(
                plugin.encode(), artifact_path.encode(),
                ";".join(parts).encode())
        else:
            # no explicit options: plain create (its env-var fallback keeps
            # working for callers that exported PADDLE_TPU_PJRT_CREATE_OPTIONS)
            self._h = self._l.pt_infer_create(plugin.encode(),
                                              artifact_path.encode())
        if not self._h:
            raise RuntimeError("pt_infer_create failed: "
                               + self._l.pt_infer_last_error().decode())
        # specs are immutable for the artifact's lifetime — read them once,
        # keeping run() free of per-call FFI spec round-trips
        self.input_specs = [self._spec(self._l.pt_infer_input_spec, i)
                            for i in range(self._l.pt_infer_input_count(self._h))]
        self.output_specs = [self._spec(self._l.pt_infer_output_spec, i)
                             for i in range(self._l.pt_infer_output_count(self._h))]

    def _spec(self, fn, i):
        dims = (ctypes.c_int64 * 16)()
        ndim = ctypes.c_int(16)
        dt = ctypes.c_int(0)
        if fn(self._h, i, dims, ctypes.byref(ndim), ctypes.byref(dt)) != 0:
            raise RuntimeError(self._l.pt_infer_last_error().decode())
        shape = tuple(dims[d] for d in range(ndim.value))
        return shape, _np_dtype(_PJRT_TYPES_INV[dt.value])

    def run(self, *inputs) -> List[np.ndarray]:
        specs = self.input_specs
        if len(inputs) != len(specs):
            raise ValueError(f"expected {len(specs)} inputs, got {len(inputs)}")
        arrs = []
        for x, (shape, dt) in zip(inputs, specs):
            a = np.ascontiguousarray(np.asarray(x), dtype=dt)
            if a.shape != shape:
                raise ValueError(f"input shape {a.shape} != spec {shape}")
            arrs.append(a)
        outs = [np.empty(shape, dt) for shape, dt in self.output_specs]
        in_ptrs = (ctypes.c_void_p * len(arrs))(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrs])
        out_ptrs = (ctypes.c_void_p * len(outs))(
            *[o.ctypes.data_as(ctypes.c_void_p).value for o in outs])
        rc = self._l.pt_infer_run(self._h, in_ptrs, len(arrs), out_ptrs,
                                  len(outs))
        if rc != 0:
            raise RuntimeError("pt_infer_run failed: "
                               + self._l.pt_infer_last_error().decode())
        return outs

    def close(self):
        if getattr(self, "_h", None):
            self._l.pt_infer_destroy(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


def build_fake_plugin(out_dir: Optional[str] = None) -> str:
    """Compile the CI fake PJRT plugin (csrc/testing/fake_pjrt_plugin.cc) and
    return its path; cached by source hash like the main native lib."""
    import hashlib

    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "csrc", "testing", "fake_pjrt_plugin.cc")
    hdr = os.path.join(here, "csrc", "third_party", "pjrt_c_api.h")
    h = hashlib.sha256()
    for p in (src, hdr):  # header is part of the ABI => part of the cache key
        with open(p, "rb") as f:
            h.update(f.read())
    tag = h.hexdigest()[:16]
    cache = out_dir or os.environ.get(
        "PADDLE_TPU_NATIVE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu"))
    os.makedirs(cache, exist_ok=True)
    so = os.path.join(cache, f"libfake_pjrt_{tag}.so")
    if not os.path.exists(so):
        tmp = f"{so}.tmp{os.getpid()}"
        subprocess.run(["g++", "-O2", "-fPIC", "-shared", "-std=c++17",
                        src, "-o", tmp], check=True, capture_output=True)
        os.replace(tmp, so)
    return so
