"""Load generator: plays a schedule against the HTTP front door.

Runs as a child process of ``run.py`` and never imports JAX, so its
clients keep off the interpreter lock of the process that drives the
chip. Asyncio clients send each request when it is due (open loop across
sessions; a session's next turn is due when its tool returns) over
``POST /generate?stream=1`` and record, per request, when it was due,
when it was sent, when each streamed chunk arrived and what it held.

    python chipbench/loadgen.py <plan.json> <records.json>

The plan holds the port, the start instant ``t0`` on the monotonic clock
(which parent and child share), the window and the drain, and the
sessions from ``generator.make_schedule``. No turn is sent that is due
after the window closes; requests in flight are followed until the drain
ends and are then recorded as unfinished.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time


async def _generate(port: int, payload: dict, rec: dict) -> None:
    """One streamed ``/generate``: status, chunk arrival times and the
    served tokens go into ``rec``."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        body = json.dumps(payload).encode()
        rec["sent"] = time.monotonic()
        writer.write(b"POST /generate?stream=1 HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\n"
                     + f"Content-Length: {len(body)}\r\n\r\n".encode()
                     + body)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        chunked = False
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            if line.lower().startswith(b"transfer-encoding: chunked"):
                chunked = True
        rec["status"] = status
        if status != 200 or not chunked:
            rec["error"] = (await reader.read())[:300].decode("latin-1")
            return
        while True:
            size = int((await reader.readline()).strip(), 16)
            if size == 0:
                break
            msg = json.loads(await reader.readexactly(size + 2))
            now = time.monotonic()
            if msg["tokens"]:
                rec["chunks"].append([now, len(msg["tokens"])])
                rec["tokens"].extend(msg["tokens"])
            if msg.get("done"):
                if msg.get("ok") is False:
                    rec["error"] = msg.get("error", "")
                    return
                rec["done"] = now
    finally:
        writer.close()


async def _session(plan: dict, sess: dict, records: list) -> None:
    t0, close = plan["t0"], plan["t0"] + plan["window"][1]
    due = t0 + sess["start"]
    prompt: list = []
    for k, turn in enumerate(sess["turns"]):
        if due >= close:
            return
        prompt = prompt + turn["add"]
        payload = {"prompt": prompt, "max_tokens": turn["max_tokens"]}
        if sess["session"]:
            payload["session_id"] = sess["sid"]
        rec = {"sid": sess["sid"], "turn": k, "due": due, "sent": None,
               "status": None, "chunks": [], "tokens": [], "done": None,
               "max_tokens": turn["max_tokens"]}
        records.append(rec)
        await asyncio.sleep(max(due - time.monotonic(), 0.0))
        try:
            await _generate(plan["port"], payload, rec)
        except (ConnectionError, OSError, ValueError,
                asyncio.IncompleteReadError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        if rec["done"] is None or len(rec["tokens"]) != turn["max_tokens"]:
            return
        prompt = prompt + rec["tokens"]
        if turn["tool_s"] is None:
            return
        due = rec["done"] + turn["tool_s"]


async def play(plan: dict) -> list:
    records: list = []
    tasks = [asyncio.ensure_future(_session(plan, s, records))
             for s in plan["sessions"]]
    end = plan["t0"] + plan["window"][1] + plan["drain_s"]
    done, pending = await asyncio.wait(
        tasks, timeout=max(end - time.monotonic(), 0.0))
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    for t in done:
        t.result()
    return records


def main(argv) -> int:
    with open(argv[1]) as f:
        plan = json.load(f)
    records = asyncio.run(play(plan))
    with open(argv[2], "w") as f:
        json.dump(records, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
