"""Model parameter serialization: pytree <-> bytes.

The reference pickles arbitrary ``dump_parameters()`` dicts to a shared volume
(reference rafiki/worker/train.py:177-183) and unpickles them in inference
workers and clients (reference rafiki/worker/inference.py:86-92,
rafiki/client/client.py:487-506). Pickle executes arbitrary code on load and
can't represent device arrays portably, so here parameters are a *pytree* of
numpy/JAX arrays + JSON-able scalars, serialized with msgpack in the layout of
flax's serialization extension (ndarray leaves as ext type 1). Device arrays
are pulled to host numpy on save; models re-shard on load.

The stream is built here and not by ``flax.serialization.msgpack_serialize``
because that copies every leaf four times on its way into one ``bytes``
(``tobytes``, the inner pack, the ext wrap, the result): six seconds a GB of
parameters that were already on the host. :func:`stream_params` yields the
same bytes as small headers between flat views of the leaves' own memory, so
a trial's parameters go from the leaves to the file (``sdk/artifact.py``)
with no buffer of the whole payload in between; :func:`dump_params` joins the
stream for the callers that want ``bytes``.
"""

from __future__ import annotations

import struct
from typing import Any, List, Tuple

import jax
import msgpack
import numpy as np
from flax import serialization

#: flax's msgpack ext type of an ndarray leaf (``_MsgpackExtType.ndarray``)
_EXT_NDARRAY = 1


def _to_host(tree: Any) -> Any:
    """Convert all array leaves to host numpy (device -> host transfer)."""

    def leaf(x):
        if isinstance(x, jax.Array):
            return np.asarray(x)
        return x

    return jax.tree_util.tree_map(leaf, tree)


def _pack(obj: Any) -> bytes:
    """A key, a scalar or any subtree that is neither a dict nor an array
    leaf, by flax's own packer: the same bytes by construction."""
    return serialization.msgpack_serialize(obj)


def _map_header(n: int) -> bytes:
    return msgpack.Packer().pack_map_header(n)


def _bin_header(n: int) -> bytes:
    if n <= 0xFF:
        return struct.pack(">BB", 0xC4, n)
    if n <= 0xFFFF:
        return struct.pack(">BH", 0xC5, n)
    return struct.pack(">BI", 0xC6, n)


def _ext_header(n: int) -> bytes:
    """msgpack's header of an ndarray ext whose data is ``n`` bytes."""
    fixed = {1: 0xD4, 2: 0xD5, 4: 0xD6, 8: 0xD7, 16: 0xD8}.get(n)
    if fixed is not None:
        return bytes((fixed, _EXT_NDARRAY))
    if n <= 0xFF:
        return struct.pack(">BBB", 0xC7, n, _EXT_NDARRAY)
    if n <= 0xFFFF:
        return struct.pack(">BHB", 0xC8, n, _EXT_NDARRAY)
    return struct.pack(">BIB", 0xC9, n, _EXT_NDARRAY)  # raises above 2**32-1


def _emit_array(flat: np.ndarray, shape: tuple, out: List[Any]) -> None:
    """One ndarray ext, ``(shape, dtype.name, bytes)``: its headers, then the
    bytes as a view of ``flat`` (1-d, C-contiguous)."""
    data = memoryview(flat.view(np.uint8))  # ml_dtypes have no buffer format
    head = (b"\x93" + msgpack.packb(shape) + msgpack.packb(flat.dtype.name)
            + _bin_header(data.nbytes))
    out.append(_ext_header(len(head) + data.nbytes) + head)
    out.append(data)


def _emit_leaf(arr: np.ndarray, out: List[Any]) -> int:
    """An array leaf as flax packs one under a dict or at the root: whole,
    or above ``MAX_CHUNK_SIZE`` as the dict of its flat chunks (contiguous
    slices, so they stream the same way). Returns the bytes that had to be
    copied: a leaf that is not C-contiguous, that leaf only."""
    shape, copied = arr.shape, 0
    if not arr.flags.c_contiguous:
        arr = np.ascontiguousarray(arr)
        copied = arr.nbytes
    flat = arr.reshape(-1)
    if arr.nbytes <= serialization.MAX_CHUNK_SIZE:
        _emit_array(flat, shape, out)
        return copied
    per_chunk = max(1, int(serialization.MAX_CHUNK_SIZE / arr.dtype.itemsize))
    starts = range(0, flat.size, per_chunk)
    out.append(b"\x83" + _pack("__msgpack_chunked_array__") + _pack(True)
               + _pack("shape")
               + _pack({str(i): d for i, d in enumerate(shape)})
               + _pack("chunks") + _map_header(len(starts)))
    for i, start in enumerate(starts):
        out.append(_pack(str(i)))
        chunk = flat[start:start + per_chunk]
        _emit_array(chunk, chunk.shape, out)
    return copied


def _emit(node: Any, out: List[Any]) -> int:
    """Append ``node``'s stream to ``out``; returns the array bytes copied."""
    if type(node) is dict:
        out.append(_map_header(len(node)))
        copied = 0
        for key, value in node.items():  # tree_map left the keys sorted
            out.append(_pack(key))
            copied += _emit(value, out)
        return copied
    if (type(node) is np.ndarray and not node.dtype.hasobject
            and node.dtype.fields is None):
        return _emit_leaf(node, out)
    out.append(_pack(node))
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(node)
               if isinstance(x, np.ndarray))


def stream_params(params: Any) -> Tuple[List[Any], int]:
    """The msgpack stream of a parameter pytree as a list of buffers, and
    the array bytes among them that are copies.

    The buffers joined equal ``flax.serialization.msgpack_serialize`` of the
    tree byte for byte. ``jax.Array`` leaves are fetched to the host here;
    every C-contiguous numpy leaf under the tree's dicts is a flat byte view
    of its own memory between small ``bytes`` of headers and keys, so the
    buffers are valid while the leaves are unchanged. Copied, and counted:
    a leaf that is not C-contiguous (the format is row-major, and a fetch
    from a TPU hands back the strides of the layout XLA chose on the device:
    a quarter of ViT-B/16's bytes), and the arrays of any subtree that is
    neither a dict nor an array (a list, a tuple, a scalar: flax's own
    packer takes those whole)."""
    out: List[Any] = []
    copied = _emit(_to_host(params), out)
    return out, copied


def dump_params(params: Any) -> bytes:
    """Serialize a parameter pytree to bytes (msgpack): the stream of
    :func:`stream_params` joined, one copy of each leaf."""
    return b"".join(stream_params(params)[0])


def load_params(data: bytes) -> Any:
    """Deserialize bytes back into a parameter pytree of numpy leaves."""
    return serialization.msgpack_restore(data)
