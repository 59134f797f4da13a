"""KV wire format v2: pool-native multi-tensor block transfer.

The v1 wire format was always DENSE: int8 pools were dequantized to bf16
before export, shipping 2x the bytes the pool actually holds — on a
transfer-bound disagg path that IS the bottleneck (whether it is, on this
installation: not measured, ROADMAP S2). v2 carries the pool-native form end to
end: a quantized pool ships ``{q8, scales}`` (≈ 0.53x the dense bf16 bytes
at head_dim 64), a dense pool ships its storage dtype, and the importer
installs whatever arrives into whatever pool it runs:

    exporter pool → importer pool   install path
    int8  → int8    verbatim q8/s scatter (bit-exact pool transfer)
    int8  → dense   device-side dequant at scatter (int8 rides H2D)
    dense → int8    device-side requant at scatter (unchanged from v1)
    dense → dense   unchanged

Schema (one streamed chunk's ``kv`` field; ``pack_array`` dicts are
msgpack/in-proc friendly):

    {"version": 2,
     "dtype": "int8" | "<dense dtype>",
     "k": pack_array, "v": pack_array,            # [n, L, BS, KH, D]
     "k_scale": pack_array, "v_scale": pack_array}  # [n, L, KH, BS] f32,
                                                    # quantized only

Negotiation: the importer's pull request carries
``{"wire": {"version": 2, "accept": [dtypes...]}}``. An exporter that sees
no ``wire`` key answers in the v1 shape (dense ``k``/``v`` fields); a v2
importer accepts both (``unpack_reply``). ``accept`` lets an importer veto
the quantized encoding (the exporter densifies before shipping).

This module is deliberately numpy-only (no jax): the recorder, the KVBM
tiers, and offline replay tooling all load it without touching a device
runtime.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

WIRE_VERSION = 2

# Wire dtype tag for quantized payloads (payload int8 + f32 scales).
WIRE_DTYPE_Q8 = "int8"


def _np_dtype(name) -> np.dtype:
    """Resolve a wire dtype (string or dtype-like), registering bfloat16
    with numpy when needed."""
    if isinstance(name, str) and "bfloat16" in name:
        import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)
    return np.dtype(name)


def pack_array(a) -> Dict[str, Any]:
    """Serialize an array zero-copy: ``b`` is a memoryview over the array's
    own buffer (cast to bytes through a uint8 view — the only layout the
    buffer protocol accepts for ml_dtypes like bfloat16). A copy happens
    ONLY when the input is not already C-contiguous."""
    arr = np.ascontiguousarray(a)
    return {
        "b": arr.view(np.uint8).reshape(-1).data,
        "shape": list(arr.shape),
        "dtype": str(arr.dtype),
    }


def unpack_array(d: Dict[str, Any]) -> np.ndarray:
    """Inverse of pack_array; zero-copy view over the received buffer."""
    return np.frombuffer(d["b"], dtype=_np_dtype(d["dtype"])).reshape(d["shape"])


def packed_nbytes(d: Optional[Dict[str, Any]]) -> int:
    """Serialized payload bytes of one pack_array dict."""
    if not d:
        return 0
    buf = d["b"]
    return buf.nbytes if isinstance(buf, memoryview) else len(buf)


@dataclass
class KvWireBlocks:
    """``n`` KV blocks in wire form (host numpy).

    Dense: ``k``/``v`` are [n, L, BS, KH, D] in ``dtype``; scales are None.
    Quantized (``dtype == "int8"``): ``k``/``v`` are int8 payloads of the
    same shape and ``k_scale``/``v_scale`` are [n, L, KH, BS] float32 —
    the pool's own per-(token, head) scales (ops/kv_quant.py layout with
    block_size on the lane axis), shipped verbatim so an int8→int8
    transfer is bit-exact."""

    dtype: str
    k: np.ndarray
    v: np.ndarray
    k_scale: Optional[np.ndarray] = None
    v_scale: Optional[np.ndarray] = None

    @classmethod
    def dense(cls, k, v) -> "KvWireBlocks":
        k, v = np.asarray(k), np.asarray(v)
        return cls(dtype=str(k.dtype), k=k, v=v)

    @property
    def quantized(self) -> bool:
        return self.dtype == WIRE_DTYPE_Q8

    def __len__(self) -> int:
        return int(self.k.shape[0])

    @property
    def nbytes(self) -> int:
        """Wire bytes: payloads + scales (what serialization actually ships)."""
        n = int(self.k.nbytes) + int(self.v.nbytes)
        if self.k_scale is not None:
            n += int(self.k_scale.nbytes)
        if self.v_scale is not None:
            n += int(self.v_scale.nbytes)
        return n

    def take(self, sel: Sequence[int]) -> "KvWireBlocks":
        """Row subset (an importer installing only the non-resident blocks).
        Returns self when ``sel`` is the identity — the common whole-chunk
        install stays copy-free."""
        if len(sel) == len(self) and list(sel) == list(range(len(self))):
            return self
        idx = np.asarray(sel, dtype=np.int64)
        return KvWireBlocks(
            dtype=self.dtype,
            k=self.k[idx],
            v=self.v[idx],
            k_scale=None if self.k_scale is None else self.k_scale[idx],
            v_scale=None if self.v_scale is None else self.v_scale[idx],
        )

    def _dequant(self, q8: np.ndarray, s: np.ndarray, dtype) -> np.ndarray:
        # [n, L, KH, BS] → [n, L, BS, KH, 1] against [n, L, BS, KH, D]
        s_t = np.swapaxes(s, -1, -2)[..., None]
        return (q8.astype(np.float32) * s_t).astype(dtype)

    def to_dense(self, dtype: Optional[str] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Dense [n, L, BS, KH, D] (k, v). Quantized payloads dequantize
        host-side to ``dtype`` (default bfloat16 — the v1 wire dtype);
        dense payloads pass through untouched unless ``dtype`` asks for a
        cast (negotiated-down exports)."""
        if not self.quantized:
            if dtype is None or _np_dtype(dtype) == self.k.dtype:
                return self.k, self.v
            out = _np_dtype(dtype)
            return self.k.astype(out), self.v.astype(out)
        out_dtype = _np_dtype(dtype or "bfloat16")
        return (
            self._dequant(self.k, self.k_scale, out_dtype),
            self._dequant(self.v, self.v_scale, out_dtype),
        )


def wire_block_bytes(
    n_layers: int,
    block_size: int,
    n_kv_heads: int,
    head_dim: int,
    wire_dtype: str,
) -> int:
    """Exact wire bytes of ONE block (k + v, scales included) for chunk
    sizing and router transfer-cost estimates. Replaces the v1
    ``kv_wire_itemsize`` (which could only express dense encodings)."""
    elems = n_layers * block_size * n_kv_heads * head_dim
    if wire_dtype == WIRE_DTYPE_Q8:
        scale_bytes = n_layers * n_kv_heads * block_size * 4  # f32 scales
        return 2 * (elems + scale_bytes)
    return 2 * elems * _np_dtype(wire_dtype).itemsize


def pack_kv(wire: KvWireBlocks) -> Dict[str, Any]:
    """One chunk's ``kv`` field (schema v2)."""
    d: Dict[str, Any] = {
        "version": WIRE_VERSION,
        "dtype": wire.dtype,
        "k": pack_array(wire.k),
        "v": pack_array(wire.v),
    }
    if wire.quantized:
        d["k_scale"] = pack_array(wire.k_scale)
        d["v_scale"] = pack_array(wire.v_scale)
    return d


def unpack_kv(d: Dict[str, Any]) -> KvWireBlocks:
    return KvWireBlocks(
        dtype=str(d["dtype"]),
        k=unpack_array(d["k"]),
        v=unpack_array(d["v"]),
        k_scale=unpack_array(d["k_scale"]) if d.get("k_scale") else None,
        v_scale=unpack_array(d["v_scale"]) if d.get("v_scale") else None,
    )


def unpack_reply(reply: Dict[str, Any]) -> Optional[KvWireBlocks]:
    """Decode one streamed transfer reply — v2 (``kv`` field) or the v1
    dense shape (separate ``k``/``v`` pack_array fields)."""
    if reply.get("kv"):
        return unpack_kv(reply["kv"])
    if reply.get("k") is not None and reply.get("v") is not None:
        return KvWireBlocks.dense(
            unpack_array(reply["k"]), unpack_array(reply["v"])
        )
    return None


def reply_wire_nbytes(reply: Dict[str, Any]) -> int:
    """Serialized KV payload bytes of one reply message (either schema)."""
    kv = reply.get("kv")
    if kv:
        return sum(
            packed_nbytes(kv.get(f)) for f in ("k", "v", "k_scale", "v_scale")
        )
    return packed_nbytes(reply.get("k")) + packed_nbytes(reply.get("v"))


def dense_tier_block(blk: Tuple[np.ndarray, ...]) -> Tuple[np.ndarray, np.ndarray]:
    """Densify a KVBM tier block: tiers store either (k, v) dense pairs or
    (k_q8, v_q8, k_scale, v_scale) quantized 4-tuples (see kvbm/tiers.py).
    Consumers that need dense arrays (the external-engine connector, the
    G4 remote write-behind) funnel through here."""
    if len(blk) == 2:
        return blk[0], blk[1]
    k_q8, v_q8, k_s, v_s = blk
    wire = KvWireBlocks(
        dtype=WIRE_DTYPE_Q8,
        k=k_q8[None],
        v=v_q8[None],
        k_scale=k_s[None],
        v_scale=v_s[None],
    )
    k, v = wire.to_dense()
    return k[0], v[0]


def tier_block_wire(blocks: Sequence[Tuple[np.ndarray, ...]]) -> KvWireBlocks:
    """Stack a uniform-form run of tier blocks into one KvWireBlocks (the
    onboard path). All blocks must share one form — callers split runs at
    form changes."""
    first = blocks[0]
    if len(first) == 2:
        return KvWireBlocks.dense(
            np.stack([b[0] for b in blocks]), np.stack([b[1] for b in blocks])
        )
    return KvWireBlocks(
        dtype=WIRE_DTYPE_Q8,
        k=np.stack([b[0] for b in blocks]),
        v=np.stack([b[1] for b in blocks]),
        k_scale=np.stack([b[2] for b in blocks]),
        v_scale=np.stack([b[3] for b in blocks]),
    )
