"""Open-loop load generator processes for the served cells (no JAX).

Each process owns a share of the client sessions. A session is one TCP
connection to the serving tier that has negotiated the binary op lane
(``hello`` with the ``binop`` cap) and sends one single-op frame at a
time: an operation due while the session still waits for its previous
reply is sent late, and its latency counts from its due time, so a
stall shows in the tail instead of thinning the load (the absolute
per-session schedule of the repository's open-loop fleet bench).

The wire format is the tier's, written out here so that the processes
never import the program: a frame is a 4-byte big-endian length and a
body; after ``hello`` every body carries one tag byte (0 = raw). A
binop request body is ``<BBHI`` (0xB1, version 1, op count, epoch 0)
followed by the opcodes (u8), slots (u32) and values (i64); the reply
is ``<BBHI`` (0xB2, flags, count, detail length), the statuses (u8),
the values (i64, when flags & 1) and a JSON detail tail.

The parent starts these processes before JAX takes the chip and talks
to each over a pipe: a job (host, port, its operations), ``ready`` once
every session is connected, the start time, then the results.
"""

from __future__ import annotations

import asyncio
import json
import resource
import struct

import numpy as np

_LEN = struct.Struct(">I")
_HEAD = struct.Struct("<BBHI")
TAG_RAW = b"\x00"


def encode_request(op: int, slot: int, value: int) -> bytes:
    body = (TAG_RAW + _HEAD.pack(0xB1, 1, 1, 0) + struct.pack(
        "<BIq", op, slot, value))
    return _LEN.pack(len(body)) + body


def decode_reply(body: bytes):
    """``(status, value_or_None)`` of a one-op binop reply."""
    if body[:1] != TAG_RAW:
        raise ValueError(f"unexpected frame tag {body[:1]!r}")
    magic, flags, n, _ = _HEAD.unpack_from(body, 1)
    if magic != 0xB2 or n != 1:
        raise ValueError(f"bad binop reply magic {magic:#x} or count {n}")
    status = body[1 + _HEAD.size]
    value = None
    if flags & 1:
        (value,) = struct.unpack_from("<q", body, 2 + _HEAD.size)
    return status, value


async def _read_frame(reader: asyncio.StreamReader) -> bytes:
    (n,) = _LEN.unpack(await reader.readexactly(4))
    return await reader.readexactly(n)


async def _connect(host: str, port: int):
    reader, writer = await asyncio.open_connection(host, port)
    hello = json.dumps({"op": "hello", "proto": 1,
                        "caps": ["binop"]}).encode()
    writer.write(_LEN.pack(len(hello)) + hello)
    await writer.drain()
    reply = json.loads(await _read_frame(reader))
    if not (reply.get("ok") and "binop" in reply.get("caps", [])):
        raise RuntimeError(f"binop lane not negotiated: {reply}")
    return reader, writer


async def _session(reader, writer, idx, t0, ops, out):
    """Run one session's operations (indices into ``ops``, due order)."""
    loop = asyncio.get_running_loop()
    due, op, slot, value = ops["due"], ops["op"], ops["slot"], ops["value"]
    for i in idx:
        at = t0 + due[i]
        wait = at - loop.time()
        if wait > 0:
            await asyncio.sleep(wait)
        out["sent"][i] = loop.time()
        writer.write(encode_request(int(op[i]), int(slot[i]),
                                    int(value[i])))
        await writer.drain()
        status, got = decode_reply(await _read_frame(reader))
        out["acked"][i] = loop.time()
        out["status"][i] = status
        if got is not None:
            out["got"][i] = got


async def _drive(conn, job) -> None:
    ops = job["ops"]
    k = len(ops["due"])
    out = {"sent": np.full(k, np.nan), "acked": np.full(k, np.nan),
           "status": np.full(k, 255, np.uint8),
           "got": np.zeros(k, np.int64)}
    sessions = job["sessions"]
    conns = []
    for base in range(0, len(sessions), 64):
        conns += await asyncio.gather(*(
            _connect(job["host"], job["port"])
            for _ in sessions[base:base + 64]))
    by_session = {s: np.nonzero(ops["session"] == s)[0] for s in sessions}
    conn.send("ready")
    # The start time is on the shared monotonic clock; the event loop's
    # clock is time.monotonic, so the schedule is absolute across
    # processes.
    t0 = conn.recv()
    tasks = [asyncio.ensure_future(_session(r, w, by_session[s], t0, ops,
                                            out))
             for s, (r, w) in zip(sessions, conns)]
    errors = []
    for t in tasks:
        try:
            await t
        except (OSError, ValueError, asyncio.IncompleteReadError) as e:
            errors.append(f"{type(e).__name__}: {e}")
    for _, w in conns:
        w.close()
    out["errors"] = errors
    conn.send(out)


def child_main(conn) -> None:
    """Process entry: serve one job from the parent's pipe."""
    _, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    job = conn.recv()
    if job is None:
        return
    asyncio.run(_drive(conn, job))
    conn.close()
