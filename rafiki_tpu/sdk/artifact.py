"""Durable artifact I/O: atomic writes + checksummed framing.

Trial params and mid-trial checkpoints are the only state that outlives a
worker process, and both used to be written with a bare ``open().write``
(params) or an un-checksummed tmp+rename (checkpoints). A torn or
bit-rotten file then surfaced as a msgpack deserialize traceback deep
inside a serving worker or a client download — long after the damage, with
no hint of the cause (the reference had the same gap: pickled params on a
shared volume, reference rafiki/worker/train.py:177-183).

This module is the single place artifact durability lives:

- :func:`atomic_write_bytes` — tmp file in the target directory, flush +
  fsync, ``os.replace``: a crash mid-write leaves the old file (or
  nothing), never a torn one. The content is bytes or a sequence of
  buffers streamed into the tmp file one by one (a trial's parameters,
  ``sdk/params.py stream_params``: no copy of the whole is ever made);
- :func:`wrap`/:func:`unwrap` — a small checksummed frame (magic +
  version + CRC32 + payload length) so damage is detected AT READ TIME
  and reported as the typed :class:`ArtifactCorruptError` instead of a
  deserialize traceback. Files written before this frame existed carry no
  magic and pass through unchanged (legacy compatibility: readers sniff).

The magic can never collide with a legacy artifact: both params and
checkpoints are msgpack maps, whose first byte is a fixmap/map16 tag
(0x80-0x8f, 0xde/0xdf) — never ASCII ``R``.
"""

from __future__ import annotations

import os
import struct
import tempfile
import zlib

#: frame layout: magic(4) | version(1) | crc32(4, BE) | payload_len(8, BE)
MAGIC = b"RFKA"
VERSION = 1
_HEADER = struct.Struct(">4sBIQ")
HEADER_SIZE = _HEADER.size


class ArtifactCorruptError(Exception):
    """A checksummed artifact failed verification (truncated, bit-rotten,
    or half-written by a crashed process). Carries the offending path so
    doors can surface a clean, typed error."""

    def __init__(self, path: str, detail: str):
        super().__init__(f"artifact {path!r} is corrupt: {detail}")
        self.path = path
        self.detail = detail


def _header(payload) -> bytes:
    return _HEADER.pack(MAGIC, VERSION, zlib.crc32(payload) & 0xFFFFFFFF,
                        len(payload))


def wrap(payload: bytes) -> bytes:
    """Frame ``payload`` with the checksummed header."""
    return _header(payload) + payload


def _verify(header: bytes, payload, path: str) -> None:
    """Raise unless ``payload`` is what the frame's ``header`` says."""
    _, version, crc, length = _HEADER.unpack(header)
    if version != VERSION:
        raise ArtifactCorruptError(
            path, f"unknown artifact frame version {version}")
    if len(payload) != length:
        raise ArtifactCorruptError(
            path, f"payload is {len(payload)} bytes, header says {length} "
                  "(truncated or half-written)")
    if zlib.crc32(payload) & 0xFFFFFFFF != crc:
        raise ArtifactCorruptError(path, "checksum mismatch (bit rot or "
                                         "torn write)")


def unwrap(data: bytes, path: str = "<bytes>") -> bytes:
    """Verify and strip the frame. Un-framed data (legacy artifacts)
    passes through unchanged — the downstream deserializer keeps owning
    that case. A non-empty strict prefix of the magic IS corruption (a
    framed file truncated inside the magic): legacy msgpack artifacts can
    never start with ASCII ``R``, so the prefix is provably not legacy."""
    if len(data) < len(MAGIC):
        if data and MAGIC.startswith(data):
            raise ArtifactCorruptError(
                path, f"truncated inside the magic ({len(data)} bytes)")
        return data
    if not data.startswith(MAGIC):
        return data
    if len(data) < HEADER_SIZE:
        raise ArtifactCorruptError(
            path, f"truncated inside the header ({len(data)} bytes)")
    payload = data[HEADER_SIZE:]
    _verify(data[:HEADER_SIZE], payload, path)
    return payload


def _buffers(data):
    """``data`` as a sequence of buffers: bytes in hand are a sequence of
    one; anything else already is one (``sdk/params.py stream_params``)."""
    if isinstance(data, (bytes, bytearray, memoryview)):
        return (data,)
    return data


def _atomic_write(path: str, data, mode: int | None, framed: bool) -> int:
    """Stream ``data``'s buffers to ``path`` via tmp + fsync + rename, under
    the checksummed frame if ``framed``; returns the bytes streamed. No
    buffer of the whole is built: each buffer goes to the tmp file as it
    comes, with the crc32 running and the length summed, behind a
    placeholder that the real header then overwrites, before the flush and
    fsync that make the file whole. A buffer (or the iteration) that raises
    removes the tmp and leaves ``path`` as it was."""
    directory = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=directory,
                               prefix=os.path.basename(path) + ".",
                               suffix=".tmp")
    crc = length = 0
    try:
        with os.fdopen(fd, "wb") as f:
            if framed:
                f.write(bytes(HEADER_SIZE))
            for buf in _buffers(data):
                if framed:
                    crc = zlib.crc32(buf, crc)
                length += memoryview(buf).nbytes
                f.write(buf)
            if framed:
                f.seek(0)
                f.write(_HEADER.pack(MAGIC, VERSION, crc & 0xFFFFFFFF, length))
            f.flush()
            os.fsync(f.fileno())
        if mode is not None:
            os.chmod(tmp, mode)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise
    # fsync the directory so the rename itself survives a host crash;
    # best-effort — not every filesystem supports directory fds
    try:
        dfd = os.open(directory, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass
    return length


def atomic_write_bytes(path: str, data, mode: int | None = None) -> None:
    """Write ``data`` (bytes, or a sequence of buffers written one after
    the other) to ``path`` via tmp + fsync + rename. Readers only ever
    observe the previous complete file or the new complete file."""
    _atomic_write(path, data, mode, framed=False)


def write_artifact(path: str, payload, mode: int | None = None) -> int:
    """Atomically persist ``payload`` inside a checksummed frame; returns
    the payload's length. ``payload`` is bytes in hand (a checkpoint, a
    sandbox child's parameters) or a sequence of buffers (a parameter
    tree's stream, gigabytes that are never joined): the file is the same,
    bit for bit."""
    return _atomic_write(path, payload, mode, framed=True)


def read_artifact(path: str) -> bytes:
    """Read and verify an artifact file; raises :class:`ArtifactCorruptError`
    on checksum/length damage, passes legacy (un-framed) files through. The
    payload of a framed file is read into its own buffer, not sliced out of
    a copy of the whole file (a model's parameters may be gigabytes)."""
    with open(path, "rb") as f:
        head = f.read(HEADER_SIZE)
        if len(head) < HEADER_SIZE or not head.startswith(MAGIC):
            return unwrap(head + f.read(), path=path)
        payload = f.read()
    _verify(head, payload, path)
    return payload
