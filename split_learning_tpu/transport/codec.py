"""Wire codec — safe, zero-copy-ish serialization of array pytrees.

The reference pickles torch tensors straight onto the wire
(``src/client_part.py:122,131,184,193``; ``src/server_part.py:39,58,74,93``)
— insecure by design (SURVEY.md §2: "must not be reproduced"). Here the wire
format is msgpack with a custom ext type for ndarrays (dtype, shape, raw
buffer): no code execution on decode, and the array payload is a raw memory
view (no base64, no copies beyond the socket).

The pytree structure is encoded as plain msgpack containers (dict/list/
scalars), so any JSON-ish tree of numpy/JAX arrays round-trips.
"""

from __future__ import annotations

import math
import threading
from typing import Any, Optional, Tuple

import msgpack
import numpy as np

_NDARRAY_EXT = 42

# allow-list of dtypes permitted on the wire (no object arrays)
_SAFE_DTYPES = frozenset(
    ["float32", "float64", "float16", "bfloat16",
     "int8", "int16", "int32", "int64",
     "uint8", "uint16", "uint32", "uint64", "bool"]
)


class CodecError(ValueError):
    pass


def _pack_array(arr: np.ndarray) -> bytes:
    name = arr.dtype.name
    if name not in _SAFE_DTYPES:
        raise CodecError(f"refusing to serialize dtype {name!r}")
    header = msgpack.packb((name, list(arr.shape)))
    return header + np.ascontiguousarray(arr).tobytes()


def _unpack_array(data: bytes) -> np.ndarray:
    unpacker = msgpack.Unpacker(max_buffer_size=len(data))
    unpacker.feed(data)
    name, shape = unpacker.unpack()
    if name not in _SAFE_DTYPES:
        raise CodecError(f"refusing to deserialize dtype {name!r}")
    offset = unpacker.tell()
    if name == "bfloat16":
        import ml_dtypes
        dtype = np.dtype(ml_dtypes.bfloat16)
    else:
        dtype = np.dtype(name)
    arr = np.frombuffer(data, dtype=dtype, offset=offset)
    return arr.reshape(shape)


def _default(obj: Any) -> Any:
    # numpy scalars also expose __array__ — check them first so they
    # round-trip as native ints/floats, not 0-d arrays
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, (np.floating, np.bool_)):
        return obj.item()
    # jax.Array and np.ndarray both expose __array__
    if hasattr(obj, "__array__") or isinstance(obj, np.ndarray):
        return msgpack.ExtType(_NDARRAY_EXT, _pack_array(np.asarray(obj)))
    raise CodecError(f"cannot serialize {type(obj)!r}")


def _ext_hook(code: int, data: bytes) -> Any:
    if code == _NDARRAY_EXT:
        return _unpack_array(data)
    raise CodecError(f"unknown ext type {code}")


def encode(obj: Any) -> bytes:
    """Pytree of dict/list/scalars/arrays -> bytes."""
    return msgpack.packb(obj, default=_default, use_bin_type=True)


def decode(data: bytes) -> Any:
    """bytes -> pytree with numpy arrays at the leaves."""
    return msgpack.unpackb(data, ext_hook=_ext_hook, raw=False,
                           strict_map_key=False)


# --------------------------------------------------------------------- #
# Optional int8 wire compression of the cut-layer payload: 4x fewer
# bytes for the 5.28 MiB hop (SURVEY.md §2 derived facts). This numpy
# path runs at the host wire boundary; native/slt_codec.cc is the same
# math in threaded C++ (parity-tested, tests/test_native.py).
# --------------------------------------------------------------------- #
_Q8_KEY = "__q8__"
_Q8_EPS = 1e-12


def _ensure_finite(a: np.ndarray, orig_dtype: Any) -> None:
    """A single NaN/Inf element poisons the symmetric scale and the whole
    tensor decodes as NaN *silently* — refuse loudly instead. Checked once
    at the wire boundary, before dispatching to either the NumPy or the
    native quantize path, so both are guarded identically."""
    if a.size and not np.isfinite(a).all():
        raise CodecError(
            f"refusing to quantize non-finite tensor "
            f"(shape={list(a.shape)}, dtype={orig_dtype})")


def q8_compress(arr: np.ndarray) -> dict:
    """float array -> {__q8__, q(int8), scale, shape, dtype}.

    Uses the multithreaded C++ kernel (native/slt_codec.cc) when it built;
    the NumPy path below is the bit-identical fallback (round-half-even,
    same scale clamp — parity-tested in tests/test_native.py)."""
    a = np.ascontiguousarray(arr, dtype=np.float32)
    _ensure_finite(a, np.asarray(arr).dtype)
    from split_learning_tpu import native
    nat = native.q8_quantize(a)
    if nat is not None:
        q, scale = nat
    else:
        scale = max(float(np.max(np.abs(a))) / 127.0, _Q8_EPS) if a.size else _Q8_EPS
        q = np.clip(np.round(a / scale), -127, 127).astype(np.int8)
    return {_Q8_KEY: True, "q": q, "scale": scale,
            "shape": list(a.shape), "dtype": str(np.asarray(arr).dtype)}


def is_q8(obj: Any) -> bool:
    return isinstance(obj, dict) and obj.get(_Q8_KEY) is True


def q8_decompress(d: dict) -> np.ndarray:
    from split_learning_tpu import native
    q8 = np.asarray(d["q"], np.int8)
    nat = native.q8_dequantize(q8, float(d["scale"]))
    if nat is not None:
        x = nat.reshape(d["shape"])
    else:
        x = (q8.astype(np.float32) * d["scale"]).reshape(d["shape"])
    name = d["dtype"]
    if name == "bfloat16":  # stock numpy can't resolve the name
        import ml_dtypes
        return x.astype(np.dtype(ml_dtypes.bfloat16))
    return x.astype(np.dtype(name))


def checksum(data: bytes) -> int:
    """Frame checksum: IEEE CRC-32 via zlib — copy-free (buffer protocol)
    and GIL-releasing, so it stays off the hot path's critical section.
    native.crc32 computes the identical value (parity-tested) but would
    copy the frame into a ctypes buffer first; zlib wins here."""
    import zlib
    return zlib.crc32(data) & 0xFFFFFFFF


def decompress_tree(obj: Any) -> Any:
    """Recursively expand any q8/topk8-compressed tensors in a decoded
    tree."""
    if is_q8(obj):
        return q8_decompress(obj)
    if is_topk8(obj):
        return topk8_decompress(obj)
    if isinstance(obj, dict):
        return {k: decompress_tree(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [decompress_tree(v) for v in obj]
    return obj


# --------------------------------------------------------------------- #
# topk8: top-k magnitude sparsification + int8 quantization of the
# survivors (the q8 scale math, applied to the selected values — the
# global |max| always survives selection, so the scale is *identical* to
# dense q8). The sender keeps the compression error in a per-tensor
# error-feedback residual (TopK8EF) that is added back before the next
# step's selection, so dropped mass is delayed, not lost (Clapping,
# arXiv:2509.19029). The wire runs this NumPy form or the multithreaded
# host fast path, native/slt_codec.cc slt_topk8_*; nothing runs in jit.
#
# Wire format ({__topk8__: True, ...}): the survivors' positions travel
# either as explicit int32 indices ("idx", 4 B/survivor — cheaper below
# ~3.1% density) or as a packed occupancy bitmap ("m", n/8 bytes total —
# cheaper above it, 0.225 B/element at the default density 0.1, a ~17x
# cut vs fp32). Both decode to the same dense tensor; the encoder always
# picks the smaller form.
# --------------------------------------------------------------------- #
_TOPK8_KEY = "__topk8__"


def _topk8_select_numpy(flat: np.ndarray, k: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Deterministic top-k-|x| selection: every element strictly above
    the k-th-largest magnitude, then threshold ties in ascending index
    order until k — exactly the native slt_topk8_select_f32 rule, so the
    two paths pick identical sets (parity-tested). Returns (ascending
    int32 indices, gathered values)."""
    n = flat.size
    if k >= n:
        idx = np.arange(n, dtype=np.int32)
        return idx, flat.copy()
    absv = np.abs(flat)
    thr = np.partition(absv, n - k)[n - k]
    gt = absv > thr
    need = k - int(np.count_nonzero(gt))
    ties = np.flatnonzero(absv == thr)[:need]
    idx = np.sort(np.concatenate([np.flatnonzero(gt), ties]))
    idx = idx.astype(np.int32)
    return idx, flat[idx]


def topk8_compress(arr: np.ndarray, density: float,
                   residual: Optional[np.ndarray] = None
                   ) -> Tuple[dict, np.ndarray]:
    """float array -> ({__topk8__, idx|m, q, scale, ...}, new_residual).

    Stateless core of the topk8 wire mode: adds ``residual`` (the error
    fed back from the previous step; None/shape-mismatch = zeros) to the
    input, selects the top ``ceil(density * n)`` magnitudes, int8-
    quantizes them with the q8 scale math, and returns the new residual
    — the full compression error x_eff - decode(packed), i.e. dropped
    values plus the survivors' quantization error."""
    if not 0.0 < density <= 1.0:
        raise CodecError(f"topk8 density must be in (0, 1] (got {density})")
    a = np.ascontiguousarray(arr, dtype=np.float32)
    _ensure_finite(a, np.asarray(arr).dtype)
    if a.size >= 2 ** 31:
        raise CodecError(
            f"topk8 indices are int32; tensor of {a.size} elements "
            "exceeds the addressable range")
    if residual is not None and residual.shape == a.shape:
        flat = (a + residual).reshape(-1)
    else:
        flat = a.copy().reshape(-1)
    n = flat.size
    d: dict = {_TOPK8_KEY: True, "n": n, "shape": list(a.shape),
               "dtype": str(np.asarray(arr).dtype)}
    if n == 0:
        d.update(idx=np.zeros(0, np.int32), q=np.zeros(0, np.int8),
                 scale=_Q8_EPS)
        return d, flat.reshape(a.shape)
    k = max(1, min(n, int(math.ceil(density * n))))

    from split_learning_tpu import native
    nat = native.topk8_select(flat, k)
    if nat is not None:
        idx, vals = nat
    else:
        idx, vals = _topk8_select_numpy(flat, k)

    # q8 scale math on the survivors (the global |max| is always among
    # them, so the scale equals dense q8's): native fast path or the
    # bit-identical NumPy fallback, same as q8_compress.
    natq = native.q8_quantize(vals)
    if natq is not None:
        q, scale = natq
    else:
        scale = max(float(np.max(np.abs(vals))) / 127.0, _Q8_EPS)
        q = np.clip(np.round(vals / scale), -127, 127).astype(np.int8)

    # error feedback: what the receiver reconstructs at the survivors is
    # q*scale — everything else (dropped mass + quantization error) stays
    # home and rides into the next step's selection
    flat[idx] -= q.astype(np.float32) * np.float32(scale)

    if n < 32 * k:  # bitmap (n/8 B) beats int32 indices (4k B)
        mask = np.zeros(n, np.bool_)
        mask[idx] = True
        d["m"] = np.packbits(mask)
    else:
        d["idx"] = idx
    d.update(q=q, scale=float(scale))
    return d, flat.reshape(a.shape)


def is_topk8(obj: Any) -> bool:
    return isinstance(obj, dict) and obj.get(_TOPK8_KEY) is True


def topk8_decompress(d: dict) -> np.ndarray:
    """{__topk8__, ...} -> dense tensor. Validates indices/bitmap against
    the declared size before touching memory — this runs on attacker-
    controllable wire bytes, like every other decode path here."""
    n = int(d["n"])
    if n < 0:
        raise CodecError(f"topk8: negative element count {n}")
    q = np.asarray(d["q"], np.int8).reshape(-1)
    if "m" in d:
        m = np.asarray(d["m"], np.uint8).reshape(-1)
        if m.size * 8 < n:
            raise CodecError(
                f"topk8: bitmap of {m.size} bytes cannot cover {n} elements")
        idx = np.flatnonzero(np.unpackbits(m, count=n))
    else:
        idx = np.asarray(d["idx"], np.int64).reshape(-1)
        if idx.size and (idx.min() < 0 or idx.max() >= n):
            raise CodecError("topk8: index out of range")
    if idx.size != q.size:
        raise CodecError(
            f"topk8: {idx.size} positions but {q.size} values")
    scale = float(d["scale"])
    from split_learning_tpu import native
    nat = native.topk8_scatter(idx, q, scale, n)
    if nat is not None:
        flat = nat
    else:
        flat = np.zeros(n, np.float32)
        flat[idx] = q.astype(np.float32) * np.float32(scale)
    x = flat.reshape(d["shape"])
    name = d["dtype"]
    if name == "bfloat16":
        import ml_dtypes
        return x.astype(np.dtype(ml_dtypes.bfloat16))
    return x.astype(np.dtype(name))


# Residual decay per tensor role. Gradients are an *additive* signal —
# what matters is the sum of updates, which full error feedback (decay 1)
# preserves exactly: measured on the 300-step CPU convergence task, topk8
# grads with full EF match the dense run to < 0.1%. Activations are not
# additive: a step-t residual added to step t+1 injects features of
# *other samples* into the forward pass, and full feedback costs ~8% final
# loss vs ~1.6% for no feedback at all. Halving the residual each step
# keeps the "dropped mass rides forward" property with a one-step
# half-life, landing at ~3% — the transports pass these per tensor role.
EF_DECAY_GRADS = 1.0
EF_DECAY_ACTS = 0.5

# tensor roles whose wire payload is a gradient (client-up "u_grads";
# the /forward_pass and /u_backward replies; the chain's backward hop
# request "hop_g" and the /hop_backward and /hop_loss replies, which
# carry the cut cotangent downstream) — everything else on the step
# path is a forward activation/feature (including "hop_x" /
# "hop_loss_x" requests and the /hop_forward reply)
_GRAD_ROLES = frozenset({"u_grads", "/forward_pass", "/u_backward",
                         "hop_g", "/hop_backward", "/hop_loss"})


def ef_decay_for(role: str) -> float:
    """Residual decay for a wire tensor role (see EF_DECAY_* above)."""
    return EF_DECAY_GRADS if role in _GRAD_ROLES else EF_DECAY_ACTS


class TopK8EF:
    """Per-tensor sender-side error-feedback residuals for topk8.

    One instance per wire endpoint: the client transport keys by
    (role, client_id); ServerRuntime.wire_ef keys by (client_id, op) so
    coalesced groups — whose per-client gradient segments are packed
    concurrently from handler threads — never share a buffer. All state
    transitions happen under one lock (coalescer-/thread-safe).

    ``decay`` scales the stored residual before it is added back
    (EF_DECAY_GRADS / EF_DECAY_ACTS above — full feedback for additive
    signals, damped for forward features).

    ``rollback(key)`` undoes the latest ``compress`` for transports whose
    send can fail after packing (an HTTP POST that never reached the
    server must not leave the shipped mass marked as delivered)."""

    def __init__(self) -> None:
        self._res: dict = {}
        self._prev: dict = {}
        self._lock = threading.Lock()

    def compress(self, key: Any, arr: np.ndarray, density: float,
                 decay: float = EF_DECAY_GRADS) -> dict:
        with self._lock:
            prev = self._res.get(key)
            fed = prev if (prev is None or decay == 1.0) else (
                np.float32(decay) * prev)
            packed, new_res = topk8_compress(arr, density, residual=fed)
            self._prev[key] = prev
            self._res[key] = new_res
            return packed

    def rollback(self, key: Any) -> None:
        with self._lock:
            if key in self._prev:
                self._res[key] = self._prev.pop(key)

    def reset(self) -> None:
        with self._lock:
            self._res.clear()
            self._prev.clear()

    # -- persistence (runtime/checkpoint.py extras sidecar) ------------- #
    def export_state(self) -> list:
        """Residual ledger as ``[{key, res}]`` records. ``_prev`` (the
        one-deep rollback buffer) is deliberately not exported: a
        rollback undoes an un-delivered send, and across a restart the
        send either landed (residual correct as stored) or the client
        retries from the replay cache without re-compressing."""
        with self._lock:
            return [{"key": list(k) if isinstance(k, tuple) else k,
                     "res": v}
                    for k, v in self._res.items()]

    def restore_state(self, entries: list) -> None:
        """Rebuild ``_res`` from :meth:`export_state` output; keys that
        exported as lists come back as the tuples compress() uses."""
        # materialize the arrays before taking the lock (SLT001: no
        # host-side copies inside the compressor's critical section)
        restored = self._restore_entries(entries)
        with self._lock:
            self._res.clear()
            self._prev.clear()
            self._res.update(restored)

    def merge_state(self, entries: list) -> int:
        """Graft another endpoint's exported residuals into this ledger
        WITHOUT touching keys that already live here — the failover
        handoff (runtime/replica.py): a dead replica's client streams
        migrate to a successor whose own streams must keep their
        residual mass. Keys present on both sides keep the local value
        (the local stream is live; the import is a stale snapshot of a
        different client set by construction). Returns how many keys
        were adopted."""
        restored = self._restore_entries(entries)
        with self._lock:
            adopted = 0
            for key, res in restored.items():
                if key not in self._res:
                    self._res[key] = res
                    adopted += 1
            return adopted

    @staticmethod
    def _restore_entries(entries: list) -> dict:
        out = {}
        for rec in entries:
            key = rec["key"]
            if isinstance(key, list):
                key = tuple(key)
            out[key] = np.asarray(rec["res"], dtype=np.float32)
        return out


class ClappingEF(TopK8EF):
    """Storage-free error feedback (Clapping, arXiv:2509.19029 §3).

    Same in-memory fold as :class:`TopK8EF` — the residual of micro-
    batch t rides into microbatch t+1's selection, so dropped mass is
    delayed one pipeline tick, never lost — but the ledger is declared
    *ephemeral*: nothing is checkpointed, nothing migrates on a PR-15
    replica handoff, and a restart simply starts folding from zero.
    The staleness this admits is exactly the delayed-gradient bound of
    pipeline-parallel optimization (arXiv:1910.05104): the residual is
    at most one selection old, and losing it on a crash costs one
    microbatch of dropped mass — the same mass a dense retransmit of
    that microbatch would have re-sent anyway.

    Concretely: ``export_state()`` is empty (so
    ``checkpoint.build_extras`` omits the ``wire_ef`` field entirely
    and the extras sidecar measurably shrinks), ``restore_state`` /
    ``merge_state`` ignore their input — a topk8-mode snapshot restored
    into a clapping endpoint does not resurrect a ledger the mode
    promised not to keep."""

    def export_state(self) -> list:
        return []

    def restore_state(self, entries: list) -> None:
        del entries  # storage-free: nothing persists, nothing restores

    def merge_state(self, entries: list) -> int:
        del entries  # handoff migrates no ledger in clapping mode
        return 0


# the EF ledger modes a wire endpoint can run; "clapping" is topk8
# selection + the storage-free ledger above
EF_MODES = ("topk8", "clapping")


def make_wire_ef(mode: str) -> TopK8EF:
    """EF ledger for ``mode`` — the one switch point every endpoint
    (ServerRuntime, StageRuntime, the client transports) routes
    through, so a mode typo fails at construction, not at handoff."""
    if mode not in EF_MODES:
        raise CodecError(
            f"unknown EF mode {mode!r} (expected one of {EF_MODES})")
    return ClappingEF() if mode == "clapping" else TopK8EF()


def compressed_leaf_bytes(obj: Any) -> Tuple[int, int]:
    """(logical_bytes, wire_bytes) summed over every q8/topk8 leaf in a
    decoded-but-not-yet-expanded tree — the compression-ratio accounting
    behind TransportStats.record_compression and the server's
    wire_compression_ratio gauge. Dense leaves contribute nothing (the
    ratio tracks what the compressor touched, not labels/scalars)."""
    if is_q8(obj) or is_topk8(obj):
        n = 1
        for s in obj["shape"]:
            n *= int(s)
        name = obj.get("dtype", "float32")
        itemsize = 2 if name == "bfloat16" else np.dtype(name).itemsize
        wire = sum(np.asarray(obj[f]).nbytes
                   for f in ("q", "idx", "m") if f in obj)
        return n * itemsize, wire
    if isinstance(obj, dict):
        vals = obj.values()
    elif isinstance(obj, list):
        vals = obj
    else:
        return 0, 0
    raw = wire = 0
    for v in vals:
        r, w = compressed_leaf_bytes(v)
        raw += r
        wire += w
    return raw, wire
