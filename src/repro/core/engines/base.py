"""Shared transfer-engine plumbing: wire helpers, Source/Sink, RecvStats.

Engines (engines/{mtedp,mt,mp}.py) move blocks between a ``Source`` and a
``Sink`` over framed TCP channels. Sources can be backed by a file, an
in-memory buffer (checkpoint leaves), or zeros (the paper's /dev/zero
mem-to-mem mode); sinks by a file, a capture buffer, or /dev/null-style
discard.

Both halves of the datapath are zero-copy:

* **send** — file-backed sources are mmapped and ``block_view(i)`` hands
  out views into the map, ``FrameBuilder`` packs headers into per-channel
  reusable buffers, and senders hand both straight to ``socket.sendmsg``
  (scatter-gather) or ``os.sendfile`` — no per-block heap copy between
  the page cache and the socket.
* **receive** — frames land directly in a registered
  ``RecvBufferPool`` (core/ringbuf.py): receivers pass pool slot views to
  ``socket.recv_into``, parse headers in place from reusable buffers, and
  the drain side hands trimmed views of the SAME pool memory to
  ``Sink.writev_views`` (coalesced ``os.pwritev``). Slot lifecycle:
  ``acquire -> recv_into -> commit -> pwritev -> release``. On Linux the
  blocking receivers can additionally opt into :class:`SpliceReceiver`
  (socket -> pipe -> file ``os.splice``), which keeps the payload
  kernel-side entirely; a :class:`SpliceUnsupported` first-call failure
  falls back to the pool path, mirroring the ``sendfile`` pattern.

Both directions additionally batch syscalls when the session negotiates
``batch_frames > 1``: senders coalesce up to that many frames into one
scatter-gather ``sendmsg`` (:func:`sendmsg_batched`, exact per-frame
delivery accounting under partial sends), and receivers drain the socket
with large slab reads parsed in place by :class:`SlabChannel` — many
frames per ``recv_into``, committed as ``(offset, view)`` pairs of the
same slab memory. Actual batch depth is hill-climbed at runtime by
``core/autotune.py``; the splice opt-in is likewise arbitrated against
the pool path by measured goodput instead of being static.
"""
from __future__ import annotations

import errno
import mmap
import os
import socket
from dataclasses import dataclass
from typing import List, Optional, Tuple

from repro.core import spans
from repro.core.header import (
    CRC_TRAILER,
    FLAG_BLOCK_CRC,
    HEADER_SIZE,
    TRAILER_SIZE,
    ChannelEvent,
    ChannelHeader,
    ProtocolError,
    pack_header_into,
)
from repro.core.integrity import (
    HAVE_NATIVE_CRC,
    buffer_address,
    crc32_update,
    crc32_update_at,
)

ACK = b"\x06"
IOV_MAX = 512
SENDFILE = hasattr(os, "sendfile")

# At-rest durability policy for received files, negotiated as the final
# Negotiation tail byte (header.Negotiation.durability). Wire bytes are
# ordered by strength so the server can apply max(client, server floor).
DURABILITY_NONE = 0  # close + ACK; the page cache owns the bytes
DURABILITY_FSYNC = 1  # fsync the sink before the final ACK
DURABILITY_ATOMIC = 2  # temp file + fsync + os.replace + dir fsync pre-ACK
DURABILITY_NAMES = ("none", "fsync", "atomic")
# receive-side temp files of atomic-mode sinks: <path>.xdfs-tmp.<pid>
TMP_INFIX = ".xdfs-tmp."


def durability_byte(policy) -> int:
    """Normalize a durability policy (name or wire byte) to its byte."""
    if isinstance(policy, str):
        try:
            return DURABILITY_NAMES.index(policy)
        except ValueError:
            raise ValueError(
                f"unknown durability policy {policy!r}; "
                f"expected one of {DURABILITY_NAMES}") from None
    b = int(policy)
    if not 0 <= b < len(DURABILITY_NAMES):
        raise ValueError(f"unknown durability byte {b}")
    return b


def fsync_dir(path: str) -> None:
    """fsync a directory so a just-landed ``os.replace`` survives power
    loss (best-effort: some filesystems refuse O_RDONLY dir fsync)."""
    try:
        fd = os.open(path or ".", os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def store_free_bytes(root: str, capacity_bytes: Optional[int] = None) -> int:
    """Bytes available for new data under ``root``. With a configured
    ``capacity_bytes`` (quota'd stores, deterministic tests) it is the
    capacity minus bytes currently stored under the root; otherwise the
    filesystem's own free space (``statvfs``)."""
    if capacity_bytes is not None:
        used = 0
        for dirpath, _dirs, files in os.walk(root):
            for name in files:
                try:
                    used += os.lstat(os.path.join(dirpath, name)).st_size
                except OSError:
                    pass
        return max(0, capacity_bytes - used)
    try:
        st = os.statvfs(root)
    except OSError:
        return 1 << 62  # unprobeable store: never refuse on a guess
    return st.f_bavail * st.f_frsize

# the one definition of which frame events end a channel's file stream
END_EVENTS = (ChannelEvent.EOFR, ChannelEvent.EOFT)


# ---------------------------------------------------------------------------
# wire helpers
# ---------------------------------------------------------------------------


MSG_MORE = getattr(socket, "MSG_MORE", 0)  # Linux: coalesce with next send


def send_all(sock: socket.socket, data, flags: int = 0) -> None:
    view = memoryview(data)
    while view:
        n = sock.send(view, flags)
        view = view[n:]


def recv_exact(sock: socket.socket, n: int, buf: Optional[memoryview] = None):
    out = memoryview(bytearray(n)) if buf is None else buf[:n]
    got = 0
    while got < n:
        r = sock.recv_into(out[got:], n - got)
        if r == 0:
            raise ConnectionError("peer closed")
        got += r
    return out


def pwrite_all(fd: int, data, offset: int) -> None:
    """``os.pwrite`` until every byte of ``data`` lands (short writes —
    near-full disk, quotas — must surface as progress or an error, never
    as a silent hole in the file)."""
    view = memoryview(data)
    while view:
        n = os.pwrite(fd, view, offset)
        if n <= 0:
            raise OSError(errno.EIO, "pwrite: short write")
        offset += n
        view = view[n:]


def advance_iovec(iov: List[memoryview], n: int) -> List[memoryview]:
    """Account ``n`` sent bytes against the head of an iovec IN PLACE —
    partial ``sendmsg`` resumes by re-slicing the vector instead of
    rebuilding the frame."""
    while n:
        head = iov[0]
        if n < len(head):
            iov[0] = head[n:]
            break
        n -= len(head)
        iov.pop(0)
    return iov


def sendmsg_all(sock: socket.socket, views) -> int:
    """Scatter-gather send of [header_view, payload_view, ...] on a blocking
    socket; partial sends re-slice the iovec until everything is on the
    wire. Returns total bytes sent."""
    iov = [v if isinstance(v, memoryview) else memoryview(v) for v in views]
    iov = [v for v in iov if len(v)]
    total = 0
    while iov:
        n = sock.sendmsg(iov)
        total += n
        advance_iovec(iov, n)
    return total


class SendfileUnsupported(OSError):
    """First ``sendfile`` call failed before any byte hit the wire — the
    fd/socket combination doesn't support it; caller falls back."""


_KERNEL_COPY_FALLBACK_ERRNOS = frozenset(
    getattr(errno, name) for name in
    ("EINVAL", "ENOSYS", "EOPNOTSUPP", "ENOTSOCK", "ENOTSUP")
    if hasattr(errno, name)
)


def sendfile_all(sock: socket.socket, fd: int, offset: int, count: int) -> int:
    """Kernel-side copy of ``count`` bytes of ``fd`` at ``offset`` into the
    socket (the uncompressed file-backed fast path). Raises
    :class:`SendfileUnsupported` only if the FIRST call fails with an
    unsupported-operation errno (nothing on the wire yet, safe to fall
    back); a mid-stream error is a real transport failure and re-raises."""
    sent = 0
    while sent < count:
        try:
            n = os.sendfile(sock.fileno(), fd, offset + sent, count - sent)
        except OSError as e:
            if sent == 0 and e.errno in _KERNEL_COPY_FALLBACK_ERRNOS:
                raise SendfileUnsupported(e.errno, "sendfile unsupported") from e
            raise
        if n == 0:
            raise ConnectionError("sendfile: peer closed")
        sent += n
    return sent


SPLICE = hasattr(os, "splice")


class SpliceUnsupported(OSError):
    """First ``splice`` call failed before any byte left the socket — the
    socket/pipe/file combination doesn't support it; caller falls back to
    the registered-buffer pool path."""


class SpliceReceiver:
    """Kernel-side socket->file block receive: ``os.splice`` through a
    private pipe (sockets cannot splice straight into a file offset), the
    receive-side mirror of the ``sendfile`` fast path. The payload never
    surfaces to user space.

    One instance per receiving worker; :meth:`splice_block` moves exactly
    one frame's payload from a BLOCKING socket into ``fd`` at ``offset``.
    Fallback contract, mirroring :func:`sendfile_all`:

    * if the FIRST socket->pipe splice of a block fails with an
      unsupported-operation errno, nothing was consumed from the socket —
      :class:`SpliceUnsupported` is raised and the caller receives the
      whole block on the generic pool path;
    * if splice dies mid-block (bytes already off the socket), the block
      is COMPLETED with a recovery copy (charged to
      ``RecvBufferPool.materializations``) and ``self.ok`` drops to False
      so the caller switches paths from the next frame — data is never
      lost to a late fallback;
    * any other mid-stream error is a real transport failure and re-raises.
    """

    PIPE_CHUNK = 1 << 16  # default Linux pipe capacity

    def __init__(self):
        if not SPLICE:
            raise SpliceUnsupported(0, "os.splice unavailable")
        self._r, self._w = os.pipe()
        self._scratch: Optional[memoryview] = None
        self.ok = True  # drops to False after a mid-block recovery

    def close(self) -> None:
        for fd in (self._r, self._w):
            try:
                os.close(fd)
            except OSError:
                pass

    def splice_block(self, sock: socket.socket, fd: int, offset: int,
                     count: int) -> int:
        """Move ``count`` payload bytes socket->pipe->file. Returns the
        number of bytes that stayed kernel-side (== ``count`` unless a
        mid-block recovery copied part of the chunk)."""
        moved = spliced = 0
        while moved < count:
            want = min(self.PIPE_CHUNK, count - moved)
            try:
                n_in = os.splice(sock.fileno(), self._w, want)
            except OSError as e:
                if e.errno not in _KERNEL_COPY_FALLBACK_ERRNOS:
                    raise
                if moved == 0:
                    raise SpliceUnsupported(
                        e.errno, "splice unsupported") from e
                self.ok = False  # finish the block in user space
                self._copy_from_socket(sock, fd, offset + moved,
                                       count - moved)
                return spliced
            if n_in == 0:
                raise ConnectionError("splice: peer closed mid-block")
            # _pipe_to_file recovers its own mid-drain fallback (dropping
            # self.ok); the whole chunk is on disk either way
            spliced += self._pipe_to_file(fd, offset + moved, n_in)
            moved += n_in
            if not self.ok:
                # finish the rest of the block from the socket, then the
                # caller drops to the pool path for later frames
                self._copy_from_socket(sock, fd, offset + moved,
                                       count - moved)
                return spliced
        return spliced

    def _pipe_to_file(self, fd: int, offset: int, n_in: int) -> int:
        """Drain ``n_in`` pipe bytes into ``fd`` at ``offset``. Returns how
        many moved kernel-side; an unsupported-errno failure mid-drain
        recovers ONLY the still-undrained remainder (at its correct
        offset) with a counted copy and drops ``self.ok``."""
        drained = 0
        while drained < n_in:
            try:
                n_out = os.splice(self._r, fd, n_in - drained,
                                  offset_dst=offset + drained)
            except OSError as e:
                if e.errno not in _KERNEL_COPY_FALLBACK_ERRNOS:
                    raise
                self.ok = False
                self._copy_from_pipe(fd, offset + drained, n_in - drained)
                return drained
            if n_out == 0:
                raise OSError(errno.EIO, "splice: pipe->file stalled")
            drained += n_out
        return drained

    def _scratch_view(self) -> memoryview:
        if self._scratch is None:
            self._scratch = memoryview(bytearray(self.PIPE_CHUNK))
        return self._scratch

    def _copy_from_pipe(self, fd: int, offset: int, n: int) -> None:
        from repro.core.ringbuf import RecvBufferPool

        RecvBufferPool.materializations += 1
        scratch = self._scratch_view()
        done = 0
        while done < n:
            got = os.readv(self._r, [scratch[: n - done]])
            if got == 0:
                raise OSError(errno.EIO, "splice recovery: pipe drained early")
            pwrite_all(fd, scratch[:got], offset + done)
            done += got

    def _copy_from_socket(self, sock: socket.socket, fd: int, offset: int,
                          n: int) -> None:
        if n <= 0:
            return
        from repro.core.ringbuf import RecvBufferPool

        RecvBufferPool.materializations += 1
        scratch = self._scratch_view()
        done = 0
        while done < n:
            got = sock.recv_into(scratch[: min(len(scratch), n - done)])
            if got == 0:
                raise ConnectionError("peer closed mid-block")
            pwrite_all(fd, scratch[:got], offset + done)
            done += got


class FrameBuilder:
    """Packs channel headers into per-channel REUSABLE buffers.

    ``depth`` is the number of header buffers per channel: a channel may
    have at most ``depth`` frames in flight (one for the legacy per-frame
    senders; the negotiated batch ceiling plus the end frame for the
    batched ones), and :meth:`header` hands the buffers out round-robin —
    the next reuse of a buffer only happens after the batch it belonged
    to fully drained. Eliminates the two per-block allocations of the
    legacy ``hdr.pack() + payload`` path (header bytes + concatenated
    frame)."""

    __slots__ = ("session", "depth", "_bufs", "_views", "_next",
                 "_tbufs", "_tviews", "_tnext")

    def __init__(self, session: bytes, n_channels: int, depth: int = 1):
        self.session = session
        self.depth = max(1, depth)
        self._bufs = [[bytearray(HEADER_SIZE) for _ in range(self.depth)]
                      for _ in range(n_channels)]
        self._views = [[memoryview(b) for b in row] for row in self._bufs]
        self._next = [0] * n_channels
        # integrity-mode CRC trailers ride the same reuse discipline: one
        # 4-byte buffer per in-flight frame, handed out round-robin
        self._tbufs = [[bytearray(TRAILER_SIZE) for _ in range(self.depth)]
                       for _ in range(n_channels)]
        self._tviews = [[memoryview(b) for b in row] for row in self._tbufs]
        self._tnext = [0] * n_channels

    def header(self, channel: int, event: ChannelEvent, offset: int,
               length: int, flags: int = 0) -> memoryview:
        slot = self._next[channel]
        self._next[channel] = (slot + 1) % self.depth
        pack_header_into(self._bufs[channel][slot], event, self.session,
                         channel, offset, length, flags)
        return self._views[channel][slot]

    def trailer(self, channel: int, crc: int) -> memoryview:
        """A packed CRC32 trailer view for the channel's next data frame."""
        slot = self._tnext[channel]
        self._tnext[channel] = (slot + 1) % self.depth
        CRC_TRAILER.pack_into(self._tbufs[channel][slot], 0, crc & 0xFFFFFFFF)
        return self._tviews[channel][slot]


@dataclass
class SendStats:
    """Delivery accounting for the batched send path. ``bytes`` counts
    bytes the kernel actually accepted (partial ``sendmsg`` returns
    included as-is); ``frames`` counts frames whose LAST byte has been
    delivered — never the raw iovec sum of an in-flight batch."""

    bytes: int = 0
    syscalls: int = 0  # sendmsg calls issued
    frames: int = 0  # frames fully delivered
    batches: int = 0  # batched sendmsg groups completed


def sendmsg_batched(sock: socket.socket, views, frame_sizes,
                    stats: Optional[SendStats] = None) -> int:
    """Scatter-gather send of MANY frames in one iovec
    (``[hdr0, blk0, hdr1, blk1, ...]``) on a blocking socket; partial
    sends resume by re-slicing (:func:`advance_iovec`). ``frame_sizes``
    holds each frame's on-wire size (header + payload); per-frame stats
    credit a frame only once the cumulative delivered byte count crosses
    its end boundary, so a short ``sendmsg`` under a tiny SO_SNDBUF never
    over-reports delivery. Returns total bytes sent."""
    iov = [v if isinstance(v, memoryview) else memoryview(v) for v in views]
    iov = [v for v in iov if len(v)]
    sent = 0
    boundary = 0  # cumulative wire size up to the next uncredited frame
    fi = 0
    while iov:
        n = sock.sendmsg(iov)
        sent += n
        if stats is not None:
            stats.syscalls += 1
            stats.bytes += n
            while fi < len(frame_sizes) and sent >= boundary + frame_sizes[fi]:
                boundary += frame_sizes[fi]
                fi += 1
                stats.frames += 1
        advance_iovec(iov, n)
    if stats is not None:
        stats.batches += 1
    return sent


# ---------------------------------------------------------------------------
# batched (slab) receive machinery
# ---------------------------------------------------------------------------


MAX_SLAB_BYTES = 8 << 20  # per-channel slab memory ceiling


def slab_span(batch_frames: int, block_size: int) -> int:
    """Slab size for a channel receiving up to ``batch_frames``-deep
    batches of ``block_size`` blocks: ideally one full batch plus a
    trailing header fits, clamped to a sane memory ceiling (a smaller
    slab stays CORRECT — frames spanning the slab edge are committed as
    partial payload views — it just flushes more often)."""
    # TRAILER_SIZE is budgeted unconditionally: integrity frames carry a
    # 4-byte CRC trailer, and a slab sized without it fills 4*batch_frames
    # bytes short of a full batch — every batch then splits its last frame
    # across an extra flush/compact round-trip
    want = batch_frames * (HEADER_SIZE + block_size + TRAILER_SIZE) + HEADER_SIZE
    return max(4 * HEADER_SIZE, min(want, MAX_SLAB_BYTES))


class SlabChannel:
    """Batched receive parser for one channel: ONE large ``recv_into``
    may land MANY frames in the slab; headers are parsed in place and
    payload ``(file_offset, view)`` pairs — views of the SAME slab
    memory — accumulate in ``pending`` for a vectored write-out.

    Frame boundaries land anywhere relative to reads: a read may end
    mid-header (the fragment waits for more bytes) or mid-payload (the
    prefix is committed immediately as a partial ``(offset, view)`` pair
    and the remainder continues in later reads, possibly after a slab
    reset). The zero-materialization invariant holds because payload
    bytes are consumed the moment they are parsed — the only bytes ever
    moved by :meth:`compact` are a sub-header tail (< 48 bytes), which is
    not a payload-sized copy.

    Caller contract: when ``free_space()`` hits 0 (or on any flush
    policy), write ``take_pending()`` out, then :meth:`compact` — views
    in ``pending`` reference slab memory and must land before the slab
    is reused. ``end_event`` is set when the channel's EOFR/EOFT frame
    is parsed; no stream bytes follow it (the ACK exchange gates the
    session's next file).
    """

    __slots__ = ("mem", "block_size", "filled", "parsed", "pending",
                 "pending_bytes", "hdr", "payload_left", "payload_off",
                 "end_event", "recv_calls", "bytes", "blocks",
                 "_crc_on", "_crc", "_trl_left", "_trl_buf",
                 "_addr", "verified", "crc_mismatches", "last_recv")

    def __init__(self, slab, block_size: int):
        # ``slab`` is a ringbuf.RecvSlab (or anything with a ``mem`` view)
        self.mem: memoryview = slab.mem
        # slab memory is fixed for the channel's lifetime, so the native
        # CRC can run from a base address computed once (the per-call
        # ctypes extraction otherwise costs ~3µs per parsed chunk)
        self._addr = buffer_address(self.mem) if HAVE_NATIVE_CRC else None
        self.block_size = block_size
        self.filled = 0
        self.parsed = 0
        self.pending: List[Tuple[int, memoryview]] = []
        self.pending_bytes = 0
        self.hdr: Optional[ChannelHeader] = None
        self.payload_left = 0
        self.payload_off = 0
        self.end_event: Optional[ChannelEvent] = None
        self.recv_calls = 0
        self.last_recv = 0
        self.bytes = 0  # payload bytes landed
        self.blocks = 0  # frames fully landed
        # integrity mode (FLAG_BLOCK_CRC frames): running payload CRC, the
        # 4-byte trailer assembled across reads, and the per-frame verdicts.
        # ``verified`` holds (offset, length, crc) of CRC-clean frames; the
        # flush path drains it into the manifest only AFTER the frame's
        # pending views are on disk (take_verified).
        self._crc_on = False
        self._crc = 0
        self._trl_left = 0
        self._trl_buf = bytearray(TRAILER_SIZE)
        self.verified: List[Tuple[int, int, int]] = []
        self.crc_mismatches = 0

    def free_space(self) -> int:
        return len(self.mem) - self.filled

    def receive_once(self, sock: socket.socket, max_bytes: int = None) -> int:
        """One ``recv_into`` into the slab's free tail, then parse
        everything that landed. Returns the number of frames COMPLETED by
        this read (the caller's FSM/stat hook). Raises ``ConnectionError``
        on EOF and propagates ``BlockingIOError`` untouched (nonblocking
        callers use it to yield).

        ``max_bytes`` caps the read below the slab's free space so a
        fair-share scheduler (the server event loop's DRR queue) can bound
        how much one channel drains per service turn. The raw byte count
        of the last read is exposed as ``last_recv``."""
        want = len(self.mem) - self.filled
        if max_bytes is not None and max_bytes < want:
            want = max_bytes
        r = sock.recv_into(self.mem[self.filled:self.filled + want])
        if r == 0:
            raise ConnectionError("peer closed mid-stream")
        self.recv_calls += 1
        self.filled += r
        self.last_recv = r
        return self._parse()

    def _parse(self) -> int:
        done = 0
        while self.end_event is None:
            if self.payload_left:
                avail = self.filled - self.parsed
                if not avail:
                    break
                take = min(self.payload_left, avail)
                chunk = self.mem[self.parsed:self.parsed + take]
                self.pending.append((self.payload_off, chunk))
                if self._crc_on:
                    if self._addr is not None:
                        self._crc = crc32_update_at(
                            self._crc, self._addr + self.parsed, take)
                    else:
                        self._crc = crc32_update(self._crc, chunk)
                self.pending_bytes += take
                self.parsed += take
                self.payload_off += take
                self.payload_left -= take
                self.bytes += take
                if self.payload_left:
                    break  # rest of this frame arrives in a later read
                if self._crc_on:
                    self._trl_left = TRAILER_SIZE  # trailer follows payload
                    continue
                self.hdr = None
                self.blocks += 1
                done += 1
                continue
            if self._trl_left:
                avail = self.filled - self.parsed
                if not avail:
                    break
                take = min(self._trl_left, avail)
                at = TRAILER_SIZE - self._trl_left
                self._trl_buf[at:at + take] = self.mem[
                    self.parsed:self.parsed + take]
                self.parsed += take
                self._trl_left -= take
                if self._trl_left:
                    break  # trailer split across reads
                (want,) = CRC_TRAILER.unpack(self._trl_buf)
                hdr = self.hdr
                if (self._crc & 0xFFFFFFFF) == want:
                    self.verified.append((hdr.offset, hdr.length, want))
                else:
                    # keep the stream synced; the manifest check at EOF
                    # reports the gap and RESUME re-fetches the block
                    self.crc_mismatches += 1
                self._crc_on = False
                self._crc = 0
                self.hdr = None
                self.blocks += 1
                done += 1
                continue
            if self.filled - self.parsed < HEADER_SIZE:
                break  # partial header: wait for more bytes
            hdr = ChannelHeader.unpack(
                self.mem[self.parsed:self.parsed + HEADER_SIZE])
            self.parsed += HEADER_SIZE
            if hdr.event in END_EVENTS:
                self.end_event = hdr.event
                break
            if hdr.length > self.block_size:
                raise ProtocolError(
                    f"block of {hdr.length} bytes exceeds negotiated "
                    f"block_size {self.block_size}"
                )
            self.hdr = hdr
            self.payload_left = hdr.length
            self.payload_off = hdr.offset
            self._crc_on = bool(hdr.flags & FLAG_BLOCK_CRC)
            self._crc = 0
        return done

    def take_pending(self) -> List[Tuple[int, memoryview]]:
        out = self.pending
        self.pending = []
        self.pending_bytes = 0
        return out

    def take_verified(self) -> List[Tuple[int, int, int]]:
        """CRC-clean ``(offset, length, crc)`` frames accumulated since
        the last call. Callers drain this into the manifest AFTER writing
        ``take_pending`` out — a frame's trailer always parses after its
        last payload chunk entered ``pending``, so at flush time every
        verified frame's bytes are on disk."""
        out = self.verified
        self.verified = []
        return out

    def compact(self) -> None:
        """Reclaim the parsed region. Only legal once ``pending`` has been
        taken AND written out (its views alias slab memory). The unparsed
        tail is always sub-header sized — payload bytes never sit
        unparsed — so this move is never a payload copy."""
        assert not self.pending, "flush pending views before compacting"
        tail = self.filled - self.parsed
        assert tail < HEADER_SIZE
        if tail:
            self.mem[0:tail] = self.mem[self.parsed:self.filled]
        self.filled = tail
        self.parsed = 0

    def seed(self, header_tail: bytes = b"", payload_off: int = 0,
             payload_left: int = 0) -> None:
        """Enter slab mode mid-stream (the mirror of :meth:`handoff`):
        ``header_tail`` pre-loads a sub-header fragment already read on
        another path; a nonzero ``payload_left`` resumes a frame whose
        prefix landed elsewhere (the remainder continues at file offset
        ``payload_off``). The two are mutually exclusive — a parser mid-
        payload never holds header bytes."""
        assert self.filled == 0 and self.payload_left == 0
        assert self._trl_left == 0
        assert not (header_tail and payload_left)
        if header_tail:
            self.mem[:len(header_tail)] = header_tail
            self.filled = len(header_tail)
        self.payload_off = payload_off
        self.payload_left = payload_left

    def handoff(self) -> Tuple[bytes, Optional[ChannelHeader], int, int]:
        """Leave slab mode at the current parse position (a datapath
        switch, e.g. the splice arbiter choosing splice back): returns
        ``(header_tail, in_progress_hdr, payload_off, payload_left)``.
        ``header_tail`` is the sub-header fragment already read (seed the
        per-frame header buffer with it); a non-None header means the
        current frame still owes ``payload_left`` bytes at file offset
        ``payload_off``. Pending must have been taken/flushed first."""
        assert not self.pending, "flush pending views before handoff"
        # datapath switches never happen mid-trailer: the splice arbiter
        # (the only handoff caller) is disabled on integrity sessions
        assert self._trl_left == 0
        tail = bytes(self.mem[self.parsed:self.filled])
        hdr, off, left = self.hdr, self.payload_off, self.payload_left
        self.hdr = None
        self.payload_left = 0
        self.filled = self.parsed = 0
        return tail, hdr, off, left


# ---------------------------------------------------------------------------
# sources and sinks
# ---------------------------------------------------------------------------


class Source:
    """Reads blocks from a file, an in-memory buffer, or serves zeros.

    File-backed sources are mmapped: :meth:`block_view` returns a
    ``memoryview`` straight into the map (zero heap copies on the send
    path), with ``os.pread`` as the fallback when the map cannot be built.
    :meth:`read_block` is the legacy materializing read; every fresh
    per-block heap copy it makes is counted in the class-level
    ``materializations`` so tests can assert the hot path stays at zero.
    """

    materializations = 0  # class-level: fresh per-block heap copies

    def __init__(self, path: Optional[str], size: int, block_size: int,
                 data: Optional[bytes] = None, use_mmap: bool = True):
        self.size = size
        self.block_size = block_size
        self.n_blocks = (size + block_size - 1) // block_size
        self.path = path
        self.data = data
        self.use_mmap = use_mmap
        self._fd = os.open(path, os.O_RDONLY) if path else -1
        self._mem = memoryview(data) if (path is None and data is not None) else None
        self._zeros = bytes(block_size) if (path is None and data is None) else None
        self._zeros_view = (memoryview(self._zeros)
                            if self._zeros is not None else None)
        self._map: Optional[mmap.mmap] = None
        self._map_view: Optional[memoryview] = None
        self._crc_addr = False  # lazily resolved base address (False=unset)
        if self._fd >= 0 and use_mmap and size > 0:
            try:
                self._map = mmap.mmap(self._fd, 0, access=mmap.ACCESS_READ)
                self._map_view = memoryview(self._map)
            except (OSError, ValueError):
                self._map = None  # pread fallback (pipes, odd filesystems)

    @property
    def file_backed(self) -> bool:
        return self._fd >= 0

    def fileno(self) -> int:
        return self._fd

    def open_worker(self) -> "Source":
        """A worker-private handle (MP/MT senders use one fd per worker)."""
        return Source(self.path, self.size, self.block_size, data=self.data,
                      use_mmap=self.use_mmap)

    def block_len(self, i: int) -> int:
        return min(self.block_size, self.size - i * self.block_size)

    def block_view(self, i: int) -> memoryview:
        """Zero-copy view of block ``i`` (mmap / in-memory / zeros); only
        the pread fallback materializes a fresh buffer."""
        ln = self.block_len(i)
        off = i * self.block_size
        if self._map_view is not None:
            return self._map_view[off : off + ln]
        if self._mem is not None:
            return self._mem[off : off + ln]
        if self._zeros_view is not None:
            return self._zeros_view[:ln]
        Source.materializations += 1
        return memoryview(os.pread(self._fd, ln, off))

    def _crc_base(self) -> Optional[int]:
        """Base address of the source's fixed backing memory (mmap or
        in-memory buffer), computed once — the map/buffer outlives the
        Source, so per-block CRCs can run straight from offsets."""
        if self._crc_addr is False:
            backing = (self._map_view if self._map_view is not None
                       else self._mem)
            self._crc_addr = (buffer_address(backing)
                              if HAVE_NATIVE_CRC and backing is not None
                              else None)
        return self._crc_addr

    def block_crc(self, i: int) -> int:
        """CRC32 of block ``i`` (integrity senders pack it into the frame
        trailer; the RESUME flow compares it against the peer's sidecar)."""
        addr = self._crc_base()
        if addr is not None:
            return crc32_update_at(0, addr + i * self.block_size,
                                   self.block_len(i))
        return crc32_update(0, self.block_view(i))

    def file_crc(self) -> int:
        """CRC32 of the whole source, computed as one sequential pass over
        the block views (mmap/in-memory — no per-block heap copies)."""
        addr = self._crc_base()
        if addr is not None:
            return crc32_update_at(0, addr, self.size)
        crc = 0
        for i in range(self.n_blocks):
            crc = crc32_update(crc, self.block_view(i))
        return crc

    def read_block(self, i: int) -> bytes:
        """Legacy materializing read (the copy path senders no longer use)."""
        ln = self.block_len(i)
        if self._fd >= 0:
            Source.materializations += 1
            return os.pread(self._fd, ln, i * self.block_size)
        if self._mem is not None:
            off = i * self.block_size
            return self._mem[off : off + ln]
        return self._zeros[:ln]

    def close(self):
        if self._map_view is not None:
            self._map_view.release()
            self._map_view = None
        if self._map is not None:
            try:
                self._map.close()
            except BufferError:
                pass  # exported block views still referenced; GC reaps later
            self._map = None
        if self._fd >= 0:
            os.close(self._fd)
            self._fd = -1


class Sink:
    """Writes blocks to a file (pwrite / coalesced pwritev), captures them
    into memory, or discards them. The zero-copy write-out is
    :meth:`writev_views`: trimmed views of registered pool memory go
    straight into ``os.pwritev`` — the pool slots they reference are
    released by the caller only after the write lands.

    ``durability`` is the negotiated at-rest policy. Engines call
    :meth:`commit` after their final flush and BEFORE the final ACK:
    ``fsync`` syncs the file, ``atomic`` lands every block in a private
    temp file (``<path>.xdfs-tmp.<pid>``) that commit fsyncs and
    ``os.replace``s over the final path (+ directory fsync) — an acked
    file can never be half-present after power loss, and a crash before
    commit leaves any previous complete version untouched. ``close``
    without commit unlinks an atomic sink's temp file."""

    def __init__(self, path: Optional[str], size: int, capture: bool = False,
                 durability=DURABILITY_NONE):
        self.path = path
        self.size = size
        self.capture = capture
        self.durability = durability_byte(durability)
        self.committed = False
        if path and self.durability >= DURABILITY_ATOMIC:
            self._write_path = f"{path}{TMP_INFIX}{os.getpid()}"
        else:
            self._write_path = path
        if path:
            self._fd = os.open(self._write_path,
                               os.O_WRONLY | os.O_CREAT, 0o644)
            os.ftruncate(self._fd, size)
            self._cap = None
        else:
            self._fd = -1
            self._cap = memoryview(bytearray(size)) if capture else None

    @property
    def data(self) -> bytes:
        """The captured payload (capture sinks only)."""
        if self._cap is None:
            raise ValueError("not a capture sink")
        return bytes(self._cap)

    @property
    def file_backed(self) -> bool:
        """True when write-out goes to a real fd (splice needs one)."""
        return self._fd >= 0

    def fileno(self) -> int:
        return self._fd

    def open_worker(self) -> "Sink":
        if self.capture:
            raise ValueError("capture sinks cannot be shared with forked workers")
        # workers write the PARENT's write path (the temp file in atomic
        # mode — never a per-worker temp) and carry no commit/cleanup
        # duty: the owning sink alone fsyncs/renames after every worker
        # is reaped
        return Sink(self._write_path, self.size)

    def write_at(self, offset: int, data) -> None:
        if self._fd >= 0:
            pwrite_all(self._fd, data, offset)
        elif self._cap is not None:
            self._cap[offset : offset + len(data)] = data

    def writev_views(self, blocks: List[Tuple[int, memoryview]]) -> int:
        """Vectored write-out of pre-trimmed ``(offset, view)`` pairs: sort
        by offset, group contiguous runs, one ``pwritev`` per run — the
        views (registered pool memory) go into the syscall untouched.

        Returns the number of vectored syscalls issued (the seek-reduction
        metric from the paper). Each call is one ``xdfs.io.write`` span
        carrying the bytes it writes."""
        if not blocks or (self._fd < 0 and self._cap is None):
            return 0
        with spans.span("io.write", sum(len(mv) for _, mv in blocks)):
            return self._writev(blocks)

    def _writev(self, blocks: List[Tuple[int, memoryview]]) -> int:
        if self._cap is not None:
            for off, mv in blocks:
                self._cap[off : off + len(mv)] = mv
            return 1
        blocks.sort(key=lambda b: b[0])
        calls = 0
        run: List[memoryview] = []
        run_start = run_end = -1
        for off, mv in blocks:
            if off == run_end and len(run) < IOV_MAX:
                run.append(mv)
                run_end += len(mv)
            else:
                if run:
                    calls += self._pwritev_all(run, run_start)
                run = [mv]
                run_start, run_end = off, off + len(mv)
        if run:
            calls += self._pwritev_all(run, run_start)
        return calls

    def _pwritev_all(self, run: List[memoryview], offset: int) -> int:
        """One run, fully written: a short ``pwritev`` (near-full disk,
        RLIMIT_FSIZE) resumes by re-slicing the iovec — a partial run must
        never silently drop its tail. Returns syscalls issued."""
        calls = 0
        while run:
            n = os.pwritev(self._fd, run, offset)
            calls += 1
            if n <= 0:
                raise OSError(errno.EIO, "pwritev: short write")
            offset += n
            advance_iovec(run, n)
        return calls

    def writev_coalesced(self, blocks: List[Tuple[int, int, bytes]]) -> int:
        """Legacy ``(offset, length, buffer)`` write-out; trims each buffer
        and delegates to :meth:`writev_views`."""
        return self.writev_views(
            [(off, memoryview(blk)[:ln]) for off, ln, blk in blocks]
        )

    def commit(self) -> None:
        """Make the received bytes durable per the sink's policy — engines
        call this after the final flush and before the final ACK, so the
        ACK is a durability promise, not just a delivery one."""
        if self._fd < 0 or self.durability == DURABILITY_NONE:
            self.committed = True
            return
        os.fsync(self._fd)
        if self.durability >= DURABILITY_ATOMIC and self._write_path != self.path:
            os.close(self._fd)
            self._fd = -1
            os.replace(self._write_path, self.path)
            fsync_dir(os.path.dirname(os.path.abspath(self.path)))
        self.committed = True

    def close(self):
        if self._fd >= 0:
            os.close(self._fd)
            self._fd = -1
        if (self.durability >= DURABILITY_ATOMIC and not self.committed
                and self._write_path != self.path):
            # aborted transfer: discard the temp file; a previous complete
            # version at the final path survives untouched
            try:
                os.unlink(self._write_path)
            except OSError:
                pass


@dataclass
class RecvStats:
    bytes: int = 0
    writev_calls: int = 0
    flushes: int = 0
    eofr_frames: int = 0  # EOFR end-frames seen (channel stays reusable)
    eoft_frames: int = 0  # EOFT end-frames seen (session terminates)
    splice_bytes: int = 0  # payload bytes that stayed kernel-side (splice)
    recv_calls: int = 0  # slab-path recv_into syscalls (0 on legacy paths)
    # times the autotuner switched a WORKING splice path off because it
    # measured slower than the pool path (mechanical fallbacks not counted)
    splice_autodisables: int = 0
    # integrity mode: data frames whose CRC32 trailer did not match the
    # payload — skipped (never written/manifested), re-fetched via RESUME
    crc_mismatches: int = 0
