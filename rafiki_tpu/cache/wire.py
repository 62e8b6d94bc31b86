"""Versioned binary wire codec for the serving data plane.

Every internal hop of the serving path (shm broker frames, the fleet
HTTP relay) used to ride ``utils/jsonutil.py``, which turns each ndarray
into float *text* (``tolist()``) — ~20 bytes and a float parse per
element, which for a dense 3072-float query is the transport's CPU, not
the model (the JSON door saturated at about a third of the binary door's
throughput on the same model). This module is the binary replacement:
ndarrays travel as raw C-contiguous bytes behind a tiny JSON header and
decode with **zero-copy** ``np.frombuffer`` views into the frame.

Frame layout (all integers little-endian)::

    [0:4]    magic  b"\\xabRWF"   (0xAB cannot start UTF-8 JSON text,
                                   so frames and JSON bodies are
                                   sniffable on one byte)
    [4]      version (currently 1)
    [5]      reserved (0)
    [6:10]   u32 header length H
    [10:10+H] header JSON: {"b": <body>, "a": [[dtype, shape, off, nbytes], ...]}
    ...      zero padding to a 16-byte boundary
    [P:]     array payload region; each array 16-byte aligned, ``off``
             relative to P

The body is an arbitrary JSON-able structure in which each ndarray was
replaced by the placeholder ``{"\\u0000nd": k}`` (index into the array
table). Dtypes are stored as ``np.dtype.str`` — byte order included —
so a big-endian array round-trips bit-exact and the decoder never
guesses endianness. Dict keys colliding with the placeholder sentinel
are escaped, so untrusted JSON queries cannot forge an array reference.

Escape hatch: values that are not numeric/bool ndarrays (strings, dicts,
object arrays…) stay inside the JSON header via the shared
``jsonutil.json_default`` convention — a frame with zero arrays is legal,
so non-array traffic rides the same framing. ``decode_any`` sniffs the
magic and falls back to plain ``json.loads``, which is what lets
old-JSON and new-binary peers interoperate on the same queue: receivers
always sniff, senders choose a format (``RAFIKI_WIRE_BINARY=0`` forces
JSON framing everywhere for a version-mismatched fleet).

All malformed input — short frames, bad version, garbled headers,
out-of-range array extents — raises :class:`WireFormatError`, never an
uncaught slice/KeyError: pop loops catch ONE exception type and a
corrupt frame can never crash a worker loop.
"""

from __future__ import annotations

import json
from typing import Any, List

import numpy as np

from rafiki_tpu.utils.jsonutil import json_default

MAGIC = b"\xabRWF"
# v1: header {"b": body, "a": array table}. v2 adds an OPTIONAL "t" key —
# request-trace metadata (utils/trace.py) riding the frame so a sampled
# predict's context crosses the shm hop without touching the body.
# Interop contract: encoders emit v1 whenever no trace metadata is
# attached (bit-identical to the old framing, so old receivers keep
# decoding) and v2 only for sampled requests; decoders accept both.
# Fleet-relay peers advertise SUPPORTED_VERSIONS on /healthz and the
# sender picks the intersection (cache/fleet.py).
VERSION = 2
# v3: the INCREMENTAL-RESPONSE message kind (generative serving,
# docs/serving-generation.md). A v3 frame is an ordinary frame whose
# header carries a "g" key — {"sid": sequence id, "fin": finished flag,
# "reason": finish reason, "err": terminal error} — and whose single
# array-table entry is the delta's token ids. Token-delta frames are
# version-marked 3 precisely so an OLD peer can never half-understand
# one: a {1,2} decoder answers the typed WireFormatError("unsupported
# wire version"), and senders consult the peer's advertised versions
# (the /healthz wire_versions handshake; the streaming door's explicit
# Accept opt-in) before ever emitting one. Non-generative traffic keeps
# emitting v1/v2 byte-identically.
TOKEN_DELTA_VERSION = 3
SUPPORTED_VERSIONS = frozenset({1, 2, 3})
_ALIGN = 16
# HTTP Content-Type for frames on the fleet relay (placement/agent.py
# negotiates it via the /healthz "wire_versions" advertisement)
CONTENT_TYPE = "application/x-rafiki-wire"

# placeholder/escape sentinels: NUL ("\\x00") cannot appear in sane user keys,
# but nothing stops a hostile JSON query from sending it — hence _ESC
_ND_KEY = "\x00nd"
_ESC_KEY = "\x00esc"

# dtype kinds that travel as raw bytes (bool, (u)int, float, complex);
# everything else falls back to the JSON escape hatch
_BINARY_KINDS = frozenset("biufc")


class WireFormatError(ValueError):
    """Frame failed to parse (truncated, garbled, unknown version)."""


def binary_enabled() -> bool:
    """Global sender-side switch: RAFIKI_WIRE_BINARY=0 forces JSON
    framing (receivers always sniff both, so this is the operator's
    escape hatch for a mixed-version fleet)."""
    import os

    return os.environ.get("RAFIKI_WIRE_BINARY", "1") not in ("0", "false")


def _pad16(n: int) -> int:
    return (-n) % _ALIGN


def _strip_arrays(obj: Any, arrays: List[np.ndarray]) -> Any:
    """Replace every binary-kind ndarray in ``obj`` with a placeholder,
    collecting the (C-contiguous) arrays; escape colliding dict keys."""
    if isinstance(obj, np.ndarray):
        if obj.dtype.kind in _BINARY_KINDS:
            a = np.ascontiguousarray(obj)
            if a.shape != obj.shape:  # ascontiguousarray promotes 0-d to 1-d
                a = a.reshape(obj.shape)
            arrays.append(a)
            return {_ND_KEY: len(arrays) - 1}
        return obj.tolist()  # str/object arrays: JSON escape hatch
    if isinstance(obj, np.generic):
        if obj.dtype.kind in _BINARY_KINDS:
            arrays.append(np.asarray(obj))  # 0-d array
            return {_ND_KEY: len(arrays) - 1}
        return obj.item()
    if isinstance(obj, dict):
        out = {k: _strip_arrays(v, arrays) for k, v in obj.items()}
        if _ND_KEY in obj or _ESC_KEY in obj:
            # a user dict that *looks like* a placeholder must never
            # decode as one (type confusion on untrusted queries)
            return {_ESC_KEY: out}
        return out
    if isinstance(obj, (list, tuple)):
        return [_strip_arrays(v, arrays) for v in obj]
    return obj


def _restore_arrays(obj: Any, views: List[np.ndarray]) -> Any:
    if isinstance(obj, dict):
        if _ND_KEY in obj:
            try:
                return views[int(obj[_ND_KEY])]
            except (IndexError, TypeError, ValueError) as e:
                raise WireFormatError(f"bad array reference: {e}") from e
        if _ESC_KEY in obj:
            inner = obj[_ESC_KEY]
            if not isinstance(inner, dict):
                raise WireFormatError("bad escape wrapper")
            return {k: _restore_arrays(v, views) for k, v in inner.items()}
        return {k: _restore_arrays(v, views) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_restore_arrays(v, views) for v in obj]
    return obj


def encode(obj: Any, trace: Any = None) -> bytes:
    """One binary frame for ``obj`` (any JSON-able structure, ndarrays
    at any depth). Raises TypeError for non-JSON, non-array leaves —
    same contract as the JSON wire convention it replaces.

    ``trace`` (a JSON-able dict, utils/trace.py wire shape) rides the v2
    frame header's "t" key; without it the frame is emitted as v1, byte
    identical to the pre-trace codec, so unsampled traffic stays
    decodable by old peers."""
    arrays: List[np.ndarray] = []
    body = _strip_arrays(obj, arrays)
    table = []
    off = 0
    for a in arrays:
        off += _pad16(off)
        table.append([a.dtype.str, list(a.shape), off, a.nbytes])
        off += a.nbytes
    hdr: dict = {"b": body, "a": table}
    version = 1
    if trace is not None:
        hdr["t"] = trace
        version = VERSION
    header = json.dumps(hdr, default=json_default).encode()
    pieces = [MAGIC, bytes([version, 0]),
              len(header).to_bytes(4, "little"), header,
              b"\x00" * _pad16(len(MAGIC) + 2 + 4 + len(header))]
    pos = 0
    for a, (_, _, o, _) in zip(arrays, table):
        if o > pos:
            pieces.append(b"\x00" * (o - pos))
            pos = o
        pieces.append(a.tobytes())  # C-contiguous by construction
        pos += a.nbytes
    return b"".join(pieces)


def is_frame(raw: bytes) -> bool:
    return len(raw) >= 4 and raw[:4] == MAGIC


def decode(raw: bytes) -> Any:
    """Decode one frame. Array leaves come back as **read-only
    zero-copy views** into ``raw`` (they keep the frame alive); callers
    that mutate must copy."""
    return decode_meta(raw)[0]


def decode_meta(raw: bytes, versions: frozenset = SUPPORTED_VERSIONS
                ) -> tuple:
    """Like :func:`decode` but returns ``(body, meta)`` where ``meta`` is
    the frame-level metadata dict — ``{"trace": ...}`` for a v2 frame
    carrying request-trace context, ``{"gen": ...}`` for a v3 token-delta
    frame, ``{}`` otherwise. ``versions`` narrows what this receiver
    accepts (tests model old peers with it; the default is everything
    this build speaks)."""
    if not is_frame(raw):
        raise WireFormatError("not a wire frame (bad magic)")
    if len(raw) < 10:
        raise WireFormatError("truncated frame header")
    if raw[4] not in versions:
        raise WireFormatError(f"unsupported wire version {raw[4]}")
    hlen = int.from_bytes(raw[6:10], "little")
    if 10 + hlen > len(raw):
        raise WireFormatError("truncated frame (header extent)")
    try:
        header = json.loads(raw[10:10 + hlen])
        body, table = header["b"], header["a"]
    except (ValueError, KeyError, TypeError, UnicodeDecodeError) as e:
        raise WireFormatError(f"garbled frame header: {e}") from e
    meta = {}
    if isinstance(header, dict) and "t" in header:
        meta["trace"] = header["t"]
    if isinstance(header, dict) and "g" in header:
        meta["gen"] = header["g"]
    payload_start = 10 + hlen + _pad16(10 + hlen)
    payload = memoryview(raw)[payload_start:]
    views: List[np.ndarray] = []
    if not isinstance(table, list):
        raise WireFormatError("garbled array table")
    for entry in table:
        try:
            dtype_str, shape, off, nbytes = entry
            dt = np.dtype(dtype_str)
            shape = tuple(int(s) for s in shape)
            off, nbytes = int(off), int(nbytes)
        except (ValueError, TypeError) as e:
            raise WireFormatError(f"garbled array entry: {e}") from e
        if dt.kind not in _BINARY_KINDS:
            raise WireFormatError(f"non-binary dtype {dtype_str!r} on wire")
        if any(s < 0 for s in shape):
            raise WireFormatError("negative array dimension")
        # Python-int product: a hostile shape like [2**32, 2**32] must
        # not wrap to 0 the way a fixed-width product would and slip
        # past the extent check
        expected = dt.itemsize
        for s in shape:
            expected *= s
        if nbytes != expected or off < 0 or off + nbytes > len(payload):
            raise WireFormatError("array extent out of range")
        try:
            views.append(np.frombuffer(
                payload[off:off + nbytes], dtype=dt).reshape(shape))
        except ValueError as e:  # belt-and-braces: numpy's own refusals
            raise WireFormatError(f"bad array extent: {e}") from e
    return _restore_arrays(body, views), meta


def decode_any(raw: bytes) -> Any:
    """The receiver-side sniff: binary frame -> :func:`decode`; anything
    else is parsed as JSON (the legacy framing). This single entry point
    is what makes every receive end mixed-version tolerant."""
    return decode_any_meta(raw)[0]


def decode_any_meta(raw: bytes) -> tuple:
    """Sniffing twin of :func:`decode_meta`: ``(body, meta)`` for frames,
    ``(json.loads(raw), {})`` for legacy JSON."""
    if is_frame(raw):
        return decode_meta(raw)
    try:
        return json.loads(raw), {}
    except (ValueError, UnicodeDecodeError) as e:
        raise WireFormatError(f"neither wire frame nor JSON: {e}") from e


# -- incremental-response message kind (generative serving) ------------------

def encode_token_delta(seq_id: str, tokens, finished: bool = False,
                       reason: Any = None, error: Any = None) -> bytes:
    """One v3 token-delta frame: sequence id + this increment's token ids
    + the finished flag (and, on the terminal delta, the finish reason /
    typed error text). The streaming door emits these to clients that
    opted in via Accept, and the shm/fleet hops may relay them to peers
    advertising wire version 3 — an old peer rejects the version byte
    with a typed WireFormatError before ever misreading the kind."""
    arr = np.ascontiguousarray(np.asarray(list(tokens), dtype=np.int32))
    g: dict = {"sid": str(seq_id), "fin": bool(finished)}
    if reason is not None:
        g["reason"] = str(reason)
    if error is not None:
        g["err"] = str(error)
    table = [[arr.dtype.str, list(arr.shape), 0, arr.nbytes]]
    header = json.dumps({"b": {_ND_KEY: 0}, "a": table, "g": g}).encode()
    return b"".join([
        MAGIC, bytes([TOKEN_DELTA_VERSION, 0]),
        len(header).to_bytes(4, "little"), header,
        b"\x00" * _pad16(len(MAGIC) + 2 + 4 + len(header)),
        arr.tobytes()])


def is_token_delta(raw: bytes) -> bool:
    """Cheap sniff: a frame whose version byte marks the incremental-
    response kind (full validation happens in :func:`decode_token_delta`)."""
    return is_frame(raw) and len(raw) >= 5 and raw[4] == TOKEN_DELTA_VERSION


def decode_token_delta(raw: bytes,
                       versions: frozenset = SUPPORTED_VERSIONS):
    """Decode one incremental-response frame into ``(seq_id,
    TokenDelta)``. Every malformed shape — missing "g" metadata, wrong
    field types, non-integer token payload, truncation — raises the one
    :class:`WireFormatError` receivers already absorb."""
    from rafiki_tpu.cache.queue import TokenDelta

    body, meta = decode_meta(raw, versions)
    g = meta.get("gen")
    if not isinstance(g, dict):
        raise WireFormatError("frame carries no token-delta metadata")
    sid, fin = g.get("sid"), g.get("fin")
    if not isinstance(sid, str) or not isinstance(fin, bool):
        raise WireFormatError("garbled token-delta metadata (sid/fin)")
    reason, err = g.get("reason"), g.get("err")
    if ((reason is not None and not isinstance(reason, str))
            or (err is not None and not isinstance(err, str))):
        raise WireFormatError("garbled token-delta metadata (reason/err)")
    if not isinstance(body, np.ndarray) or body.dtype.kind not in "iu":
        raise WireFormatError("token-delta payload is not an integer array")
    return sid, TokenDelta([int(t) for t in body.ravel()],
                           finished=fin, reason=reason, error=err)


def dumps(obj: Any, trace: Any = None) -> bytes:
    """Sender-side entry point: binary frame, or the legacy JSON framing
    when RAFIKI_WIRE_BINARY=0 (trace metadata rides only the binary
    frame header — the JSON escape hatch predates it)."""
    if binary_enabled():
        return encode(obj, trace=trace)
    return json.dumps(obj, default=json_default).encode()


# -- content digests (prediction result cache) -------------------------------

def canonical_digest(obj: Any) -> "str | None":
    """Stable content hash of one query for the prediction result cache
    (predictor/result_cache.py): two byte-identical queries must map to
    one digest however they arrived. Array-bearing payloads ride the
    v1 binary encoding (dtype + shape + raw bytes — the same canonical
    form every serving hop already speaks, so a binary-door query and
    its JSON-door twin hash alike once decoded); everything else falls
    back to sorted-key canonical JSON. Returns ``None`` for payloads
    with no canonical encoding (exotic objects) — the cache treats those
    as permanently uncacheable, never an error on the serving path.

    Collision stance: blake2b-128 over the canonical bytes. A cache hit
    substitutes one model forward for another, so the only damage a
    collision could do is serve query A's prediction to query B — at
    2^64 birthday cost that is not a realistic event, and the cache is
    flushed on every model-version change regardless.
    """
    import hashlib

    try:
        if isinstance(obj, np.ndarray) or _has_array(obj):
            raw = encode(obj)
        else:
            raw = json.dumps(obj, sort_keys=True,
                             separators=(",", ":")).encode()
    except (TypeError, ValueError):
        return None
    return hashlib.blake2b(raw, digest_size=16).hexdigest()


def _has_array(obj: Any, depth: int = 0) -> bool:
    """True when ``obj`` carries an ndarray/numpy scalar anywhere a
    frame encoder would find one (bounded depth — a pathological deep
    query just takes the JSON fallback)."""
    if depth > 8:
        return False
    if isinstance(obj, (np.ndarray, np.generic)):
        return True
    if isinstance(obj, dict):
        return any(_has_array(v, depth + 1) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return any(_has_array(v, depth + 1) for v in obj)
    return False


def stackable(queries: List[Any]) -> bool:
    """True when ``queries`` is a non-empty homogeneous batch of numeric
    ndarrays (same dtype+shape) — the single definition of 'stackable'
    shared by every hop that turns a request's rows into one contiguous
    array (shm framing, fleet relay, worker batch assembly)."""
    first = queries[0] if queries else None
    return (isinstance(first, np.ndarray)
            and first.dtype.kind in _BINARY_KINDS
            and all(isinstance(q, np.ndarray) and q.dtype == first.dtype
                    and q.shape == first.shape for q in queries))


def stack_batch(queries: List[Any]) -> Any:
    """One ``(n, ...)`` array for a stackable batch (zero-copy for the
    single-row case), or None when the batch is not stackable."""
    if not stackable(queries):
        return None
    return queries[0][None] if len(queries) == 1 else np.stack(queries)
