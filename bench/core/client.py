"""The load generator: a process of its own that never imports JAX.

    python bench/core/client.py < spec.json

reads one JSON object on standard input (host, port, the mix, seed,
window seconds), sends the mix's requests as streaming
``POST /v1/completions`` over loopback HTTP, and times each at the
client's wall clock (``time.monotonic``, which every process on the
host shares).  Its standard output carries two JSON lines: ``{"start":
t0}`` when the ramp begins, and at the end ``{"records": [...], ...}``.

Open loop: request i is due at ``t0 + due_i`` and is timed from then,
so a late generator is charged to the server's latency; the lateness is
reported.  Closed loop: ``concurrency`` clients each send their next
request when the previous one ends, timed from the send; their first
sends are spread evenly over the loop's ``stagger_s`` (0 if absent), so
that requests of equal size do not start, and end, in one wave.  After the
window closes no new request starts; requests due in the window are
followed for at most the mix's ``drain_cap_s``, then every connection is
closed; a request still running then is marked ``late``.
"""
from __future__ import annotations

import asyncio
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import traffic as traffic_lib                      # noqa: E402

MARKER = re.compile("⟨(\\d+)⟩")


def count_tokens(text: str) -> int:
    """Tokens in a streamed delta of the byte tokenizer: an id of 256 or
    more arrives as a marker, a byte as its character (a UTF-8 sequence
    as one character of that many bytes, a broken one as one U+FFFD)."""
    n = len(MARKER.findall(text))
    for ch in MARKER.sub("", text):
        n += 1 if ch == "�" else len(ch.encode("utf-8"))
    return n


class Record:
    """What the client saw of one request."""

    __slots__ = ("index", "due", "sent", "status", "first", "last",
                 "tokens", "frames", "text", "server_id", "finish",
                 "error", "ended", "late")

    def __init__(self, index: int, due: float):
        self.index = index
        self.due = due
        self.sent = None
        self.status = None
        self.first = None
        self.last = None
        self.tokens = 0
        self.frames = []          # (time, tokens) per frame with text
        self.text = []
        self.server_id = None
        self.finish = None
        self.error = None
        self.ended = None
        self.late = False         # still running when the drain cap ran out

    def as_dict(self, t0: float) -> dict:
        rel = lambda t: None if t is None else t - t0
        return {"index": self.index, "due": rel(self.due),
                "sent": rel(self.sent), "status": self.status,
                "first": rel(self.first), "last": rel(self.last),
                "tokens": self.tokens, "text": "".join(self.text),
                "server_id": self.server_id, "finish": self.finish,
                "error": self.error, "ended": rel(self.ended),
                "late": self.late}


async def _read_head(reader) -> int:
    head = await reader.readuntil(b"\r\n\r\n")
    return int(head.split(b" ", 2)[1])


async def send(host: str, port: int, req: traffic_lib.Planned,
               rec: Record):
    """One streaming completion; fills ``rec`` as frames arrive."""
    body = json.dumps({"prompt": req.prompt, "max_tokens": req.max_tokens,
                       "stream": True}).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        rec.sent = time.monotonic()
        writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: %d\r\n\r\n" % len(body) + body)
        await writer.drain()
        rec.status = await _read_head(reader)
        if rec.status != 200:
            rec.error = (await reader.read(4096)).decode(errors="replace")
            return
        done = False
        while True:
            size = int((await reader.readuntil(b"\r\n")).strip(), 16)
            if size == 0:
                break
            frame = (await reader.readexactly(size + 2))[:-2]
            now = time.monotonic()
            for ev in frame.split(b"\n\n"):
                if not ev.startswith(b"data: "):
                    continue
                payload = ev[len(b"data: "):]
                if payload == b"[DONE]":
                    done = True
                    continue
                obj = json.loads(payload)
                if "choices" not in obj:
                    rec.error = json.dumps(obj)
                    continue
                if rec.server_id is None:
                    rec.server_id = obj.get("id")
                choice = obj["choices"][0]
                text = choice.get("text") or ""
                if text:
                    n = count_tokens(text)
                    rec.text.append(text)
                    rec.tokens += n
                    rec.frames.append((now, n))
                    if rec.first is None:
                        rec.first = now
                    rec.last = now
                if choice.get("finish_reason"):
                    rec.finish = choice["finish_reason"]
        if not done and rec.error is None:
            rec.error = "stream ended without [DONE]"
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        rec.error = rec.error or repr(e)
    finally:
        rec.ended = time.monotonic()
        if writer is not None:
            writer.close()


async def drive(spec: dict) -> dict:
    mix = spec["traffic"]
    seconds = float(spec["seconds"])
    reqs = traffic_lib.plan(mix, int(spec["seed"]), seconds)
    host, port = spec["host"], int(spec["port"])
    ramp, cap = float(mix["ramp_s"]), float(mix["drain_cap_s"])
    t0 = time.monotonic()
    print(json.dumps({"start": t0}), flush=True)
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    records, tasks = [], []
    lateness = []
    if mix["loop"]["kind"] == "open":
        for req in reqs:
            due = t0 + req.due
            if due > w1:
                break
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            lateness.append(max(time.monotonic() - due, 0.0))
            rec = Record(req.index, due)
            records.append(rec)
            tasks.append(asyncio.create_task(send(host, port, req, rec)))
        await asyncio.sleep(max(w1 - time.monotonic(), 0.0))
    else:
        pool = iter(reqs)
        conc = int(mix["loop"]["concurrency"])
        stagger = float(mix["loop"].get("stagger_s", 0.0))

        async def client(i):
            await asyncio.sleep(i * stagger / conc)
            while time.monotonic() < w1:
                try:
                    req = next(pool)
                except StopIteration:
                    return
                rec = Record(req.index, None)
                records.append(rec)
                await send(host, port, req, rec)

        tasks = [asyncio.create_task(client(i)) for i in range(conc)]
        await asyncio.sleep(max(w1 - time.monotonic(), 0.0))
    # follow the requests due in the window, up to the drain cap
    in_window = lambda r: w0 <= (r.due if r.due is not None
                                 else (r.sent or 0.0)) <= w1
    deadline = w1 + cap
    while time.monotonic() < deadline:
        if all(r.ended is not None for r in records if in_window(r)):
            break
        await asyncio.sleep(0.05)
    drained_at = time.monotonic()
    for r in records:
        r.late = r.ended is None
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for r in records:
        if r.due is None:
            r.due = r.sent
    tokens_in_window = sum(n for r in records for (t, n) in r.frames
                           if w0 <= t <= w1)
    return {"records": [r.as_dict(t0) for r in records],
            "window": [ramp, ramp + seconds],
            "drain_s": drained_at - w1,
            "tokens_in_window": tokens_in_window,
            "lateness_max_s": max(lateness, default=0.0),
            "lateness_mean_s": (sum(lateness) / len(lateness)
                                if lateness else 0.0)}


def main():
    spec = json.loads(sys.stdin.read())
    out = asyncio.run(drive(spec))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
