"""Tensor wire protocol (paper Fig. 2), production-hardened.

The paper frames tensors on a TCP socket as ``dtype, shape, raw values``.
We keep that exact framing, add a magic/version header and a CRC32 trailer
(integrity matters once this carries checkpoints), and extend it to pytrees.

Frame layout (little-endian)::

    u32  magic        0x52505257  ("RPRW")
    u8   version      1
    u8   dtype_code   (see DTYPE_CODES)
    u16  rank
    u64  dim[rank]
    u8   payload[prod(dims) * itemsize]   (C-order raw values)
    u32  crc32(payload)

A *pytree frame* is a JSON header frame (dtype_code=255 carrying UTF-8) with
the treedef + leaf count, followed by one tensor frame per leaf.

This codec is used by: the checkpoint store, the elastic control plane, and
the tool-offload RPC — i.e. everywhere the paper used its socket protocol
except the activation plane (which on TPU is `lax.ppermute`, see DESIGN §8).
"""

from __future__ import annotations

import io
import json
import struct
import sys
import zlib
from typing import Any, BinaryIO, List, Tuple

import jax
import numpy as np

MAGIC = 0x52505257
VERSION = 1

# dtype_code -> numpy dtype. bfloat16 is serialised via its uint16 bit pattern.
DTYPE_CODES = {
    0: np.dtype(np.float32),
    1: np.dtype(np.float64),
    2: np.dtype(np.float16),
    3: np.dtype(np.int8),
    4: np.dtype(np.int16),
    5: np.dtype(np.int32),
    6: np.dtype(np.int64),
    7: np.dtype(np.uint8),
    8: np.dtype(np.uint16),
    9: np.dtype(np.uint32),
    10: np.dtype(np.uint64),
    11: np.dtype(np.bool_),
    12: "bfloat16",  # special-cased
    255: None,       # JSON header frame
}
_CODE_FOR: dict = {}
for _c, _d in DTYPE_CODES.items():
    if isinstance(_d, np.dtype):
        _CODE_FOR[_d] = _c
_BF16_CODE = 12
_JSON_CODE = 255

_HDR = struct.Struct("<IBBH")  # magic, version, dtype_code, rank


class WireError(ValueError):
    pass


def _np_bf16():
    import ml_dtypes  # ships with jax

    return np.dtype(ml_dtypes.bfloat16)


def encode_tensor(arr: Any, out: BinaryIO) -> int:
    """Encode one array as a wire frame. Returns bytes written."""
    arr = np.asarray(arr)
    if arr.dtype == _np_bf16():
        code = _BF16_CODE
        payload_arr = arr.view(np.uint16)
    else:
        try:
            code = _CODE_FOR[arr.dtype]
        except KeyError:
            raise WireError(f"unsupported dtype {arr.dtype}")
        payload_arr = arr
    payload = np.ascontiguousarray(payload_arr).tobytes()
    n = out.write(_HDR.pack(MAGIC, VERSION, code, arr.ndim))
    n += out.write(struct.pack(f"<{arr.ndim}Q", *arr.shape))
    n += out.write(payload)
    n += out.write(struct.pack("<I", zlib.crc32(payload) & 0xFFFFFFFF))
    return n


def _read_exact(src: BinaryIO, n: int) -> bytes:
    if not 0 <= n <= sys.maxsize:      # a corrupted length or shape field
        raise WireError(f"bad frame length {n}")
    buf = src.read(n)
    if len(buf) != n:
        raise WireError(f"truncated frame: wanted {n} bytes, got {len(buf)}")
    return buf


def decode_tensor(src: BinaryIO) -> np.ndarray:
    magic, version, code, rank = _HDR.unpack(_read_exact(src, _HDR.size))
    if magic != MAGIC:
        raise WireError(f"bad magic {magic:#x}")
    if version != VERSION:
        raise WireError(f"unsupported version {version}")
    shape = struct.unpack(f"<{rank}Q", _read_exact(src, 8 * rank)) if rank else ()
    if code == _JSON_CODE:
        raise WireError("unexpected JSON frame; use decode_pytree")
    if code == _BF16_CODE:
        np_dtype, view_as = np.dtype(np.uint16), _np_bf16()
    else:
        try:
            np_dtype, view_as = DTYPE_CODES[code], None
        except KeyError:
            raise WireError(f"unknown dtype code {code}")
    count = int(np.prod(shape, dtype=np.int64)) if shape else 1
    payload = _read_exact(src, count * np_dtype.itemsize)
    (crc,) = struct.unpack("<I", _read_exact(src, 4))
    if crc != (zlib.crc32(payload) & 0xFFFFFFFF):
        raise WireError("payload CRC mismatch")
    arr = np.frombuffer(payload, dtype=np_dtype).reshape(shape)
    if view_as is not None:
        arr = arr.view(view_as)
    return arr.copy()  # own the memory


def _encode_json(obj: Any, out: BinaryIO) -> int:
    payload = json.dumps(obj, sort_keys=True).encode("utf-8")
    n = out.write(_HDR.pack(MAGIC, VERSION, _JSON_CODE, 1))
    n += out.write(struct.pack("<1Q", len(payload)))
    n += out.write(payload)
    n += out.write(struct.pack("<I", zlib.crc32(payload) & 0xFFFFFFFF))
    return n


def _decode_json(src: BinaryIO) -> Any:
    magic, version, code, rank = _HDR.unpack(_read_exact(src, _HDR.size))
    if magic != MAGIC or code != _JSON_CODE or rank != 1:
        raise WireError("expected JSON frame")
    (length,) = struct.unpack("<1Q", _read_exact(src, 8))
    payload = _read_exact(src, length)
    (crc,) = struct.unpack("<I", _read_exact(src, 4))
    if crc != (zlib.crc32(payload) & 0xFFFFFFFF):
        raise WireError("JSON CRC mismatch")
    return json.loads(payload.decode("utf-8"))


def encode_pytree(tree: Any, out: BinaryIO) -> int:
    """Encode an arbitrary pytree of arrays (+ scalar ints/floats)."""
    leaves, treedef = jax.tree.flatten(tree)
    header = {"treedef": _treedef_to_json(treedef), "n_leaves": len(leaves)}
    n = _encode_json(header, out)
    for leaf in leaves:
        n += encode_tensor(np.asarray(leaf), out)
    return n


def decode_pytree(src: BinaryIO) -> Any:
    header = _decode_json(src)
    leaves = [decode_tensor(src) for _ in range(header["n_leaves"])]
    treedef = _treedef_from_json(header["treedef"])
    return jax.tree.unflatten(treedef, leaves)


def dumps(tree: Any) -> bytes:
    buf = io.BytesIO()
    encode_pytree(tree, buf)
    return buf.getvalue()


def loads(data: bytes) -> Any:
    return decode_pytree(io.BytesIO(data))


# --- treedef <-> JSON (dict/list/tuple/leaf structures only) ----------------
class _Leaf:
    """Sentinel marking a leaf position (distinct from a literal None node)."""


_LEAF = _Leaf()


def _treedef_to_json(treedef) -> Any:
    # Round-trip through an example tree of sentinels: structure only.
    example = jax.tree.unflatten(treedef, [_LEAF] * treedef.num_leaves)
    return _structure_to_json(example)


def _structure_to_json(obj: Any) -> Any:
    if obj is _LEAF:
        return {"t": "leaf"}
    if obj is None:
        return {"t": "none"}
    if isinstance(obj, dict):
        return {"t": "dict", "k": sorted(obj.keys()),
                "v": [_structure_to_json(obj[k]) for k in sorted(obj.keys())]}
    if isinstance(obj, tuple):
        return {"t": "tuple", "v": [_structure_to_json(x) for x in obj]}
    if isinstance(obj, list):
        return {"t": "list", "v": [_structure_to_json(x) for x in obj]}
    raise WireError(f"unsupported pytree node {type(obj)}")


def _json_to_structure(spec: Any) -> Any:
    t = spec["t"]
    if t == "leaf":
        return _LEAF
    if t == "none":
        return None
    if t == "dict":
        return {k: _json_to_structure(v) for k, v in zip(spec["k"], spec["v"])}
    if t == "tuple":
        return tuple(_json_to_structure(v) for v in spec["v"])
    if t == "list":
        return [_json_to_structure(v) for v in spec["v"]]
    raise WireError(f"bad structure spec {t}")


def _treedef_from_json(spec: Any):
    example = _json_to_structure(spec)
    return jax.tree.structure(example, is_leaf=lambda x: x is _LEAF)
