"""The load generator: sends on the schedule, chains a session's turns
after its tool time, records chunk times and tokens, and never imports
JAX."""
import asyncio
import json
import subprocess
import sys
import threading
import time

from run import BENCH


class StubServer:
    """``/generate?stream=1`` that streams ``max_tokens`` tokens (the
    prompt's length, repeated) in two chunks, 50 ms apart."""

    def __init__(self):
        self.prompts = []
        self.loop = asyncio.new_event_loop()
        ready = threading.Event()
        self.thread = threading.Thread(target=self._run, args=(ready,),
                                       daemon=True)
        self.thread.start()
        ready.wait(10)

    def _run(self, ready):
        asyncio.set_event_loop(self.loop)
        srv = self.loop.run_until_complete(
            asyncio.start_server(self._handle, "127.0.0.1", 0))
        self.port = srv.sockets[0].getsockname()[1]
        ready.set()
        self.loop.run_forever()

    async def _handle(self, reader, writer):
        await reader.readline()
        n = 0
        while (line := await reader.readline()) not in (b"\r\n", b""):
            if line.lower().startswith(b"content-length"):
                n = int(line.split(b":")[1])
        body = json.loads(await reader.readexactly(n))
        self.prompts.append(body)
        writer.write(b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n\r\n")
        k, m = len(body["prompt"]), body["max_tokens"]

        def chunk(obj):
            data = (json.dumps(obj) + "\n").encode()
            return f"{len(data):x}\r\n".encode() + data + b"\r\n"
        writer.write(chunk({"tokens": [k] * (m // 2), "done": False}))
        await writer.drain()
        await asyncio.sleep(0.05)
        writer.write(chunk({"tokens": [k] * (m - m // 2), "done": True}))
        writer.write(b"0\r\n\r\n")
        await writer.drain()
        writer.close()

    def stop(self):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)


def test_plays_sessions_and_never_imports_jax(tmp_path):
    srv = StubServer()
    t0 = time.monotonic() + 0.5
    sessions = [
        {"sid": "a", "start": 0.0, "session": True, "turns": [
            {"add": [1, 2, 3], "max_tokens": 4, "tool_s": 0.2},
            {"add": [9], "max_tokens": 2, "tool_s": None}]},
        {"sid": "b", "start": 0.1, "session": False, "turns": [
            {"add": [5] * 7, "max_tokens": 3, "tool_s": None}]},
        # starts after the window: never sent
        {"sid": "c", "start": 5.0, "session": False, "turns": [
            {"add": [5], "max_tokens": 3, "tool_s": None}]},
    ]
    plan = {"port": srv.port, "t0": t0, "window": [0.0, 2.0],
            "drain_s": 5.0, "sessions": sessions}
    (tmp_path / "plan.json").write_text(json.dumps(plan))
    code = ("import runpy, sys\n"
            f"sys.argv = ['loadgen', {str(tmp_path / 'plan.json')!r}, "
            f"{str(tmp_path / 'rec.json')!r}]\n"
            "try:\n"
            f"    runpy.run_path({str(BENCH / 'loadgen.py')!r}, "
            "run_name='__main__')\n"
            "except SystemExit as e:\n"
            "    assert not e.code\n"
            "print('jax' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    srv.stop()
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
    recs = {(r["sid"], r["turn"]): r
            for r in json.loads((tmp_path / "rec.json").read_text())}
    assert set(recs) == {("a", 0), ("a", 1), ("b", 0)}
    a0, a1 = recs[("a", 0)], recs[("a", 1)]
    assert a0["status"] == 200 and a0["tokens"] == [3] * 4
    assert len(a0["chunks"]) == 2 and a0["done"] == a0["chunks"][-1][0]
    assert abs(a0["due"] - t0) < 1e-9 and a0["sent"] >= a0["due"]
    # the next turn is due the tool's time after the turn ended, and
    # resends the context: prompt, served tokens, the tool's result
    assert abs(a1["due"] - (a0["done"] + 0.2)) < 1e-9
    assert a1["tokens"] == [3 + 4 + 1] * 2
    assert recs[("b", 0)]["tokens"] == [7] * 3
    sent = {json.dumps(p.get("session_id")) for p in srv.prompts}
    assert sent == {'"a"', "null"}
