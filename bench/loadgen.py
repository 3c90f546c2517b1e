"""The online cells' load generator, in a process of its own.

    python bench/loadgen.py <plan.json> <result.json>

It imports nothing but the standard library (no JAX), so it shares no
interpreter, lock or event loop with the server it loads. The plan gives
the server's port, the warm-up requests and the timed requests, each
with its prompt, its output length and when it is due (seconds after
the base time); ``w0`` and ``w1`` bound the measured window on the same
scale.

1. The warm-up requests go one after another, each to its end.
2. The base time is set and printed on standard output as one JSON line,
   ``{"base": ..., "warm_sent": ...}``, in ``time.monotonic()`` seconds,
   which every process on the host reads alike.
3. Every timed request is sent when it is due, on its own connection,
   and its streamed tokens are timed as they arrive.
4. After ``w1`` it waits, at most ``drain_s``, until every request due in
   the window has its first token, then disconnects the rest and writes
   each request's send time, token times, tokens, end and error to the
   result file.

Exits 0 once the result is written, 1 if a warm-up request failed.
"""
import asyncio
import json
import sys
import time


def _new(spec, due):
    return {"due": due, "prompt": spec["prompt"], "n_out": spec["n_out"],
            "sent": None, "times": [], "tokens": [], "done": False,
            "error": None}


async def stream(port: int, req: dict) -> None:
    """One request: sent at its due time, its tokens timed on arrival."""
    await asyncio.sleep(max(req["due"] - time.monotonic(), 0.0))
    req["sent"] = time.monotonic()
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        body = json.dumps({"prompt": req["prompt"],
                           "max_new_tokens": req["n_out"]})
        writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: bench\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n{body}").encode())
        await writer.drain()
        status = await reader.readline()
        if b" 200 " not in status:
            req["error"] = status.decode(errors="replace").strip()
            return
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        while True:
            size = int((await reader.readline()).strip() or b"0", 16)
            if size == 0:
                break
            item = json.loads(await reader.readexactly(size))
            await reader.readexactly(2)
            if "token" in item:
                req["times"].append(time.monotonic())
                req["tokens"].append(int(item["token"]))
            elif item.get("event") == "end":
                req["done"] = bool(item.get("done"))
        if not req["done"]:
            req["error"] = "stream ended before its last token"
    finally:
        writer.close()


async def run(plan: dict) -> dict:
    port = plan["port"]
    warm = [_new(w, 0.0) for w in plan["warm"]]
    for r in warm:
        r["due"] = time.monotonic()
        await stream(port, r)
        if not r["done"]:
            raise RuntimeError(f"warm-up request failed: {r['error']}")
    base = time.monotonic() + 0.05
    hello = {"base": base, "warm_sent": warm[0]["sent"]}
    print(json.dumps(hello), flush=True)
    reqs = [_new(x, base + x["due"]) for x in plan["requests"]]
    tasks = [asyncio.ensure_future(stream(port, r)) for r in reqs]
    w0, w1 = base + plan["w0"], base + plan["w1"]
    await asyncio.sleep(max(w1 - time.monotonic(), 0.0))
    limit = time.monotonic() + plan["drain_s"]
    while time.monotonic() < limit and any(
            not r["times"] and r["error"] is None and not tk.done()
            for r, tk in zip(reqs, tasks) if w0 <= r["due"] < w1):
        await asyncio.sleep(0.02)
    for tk in tasks:
        tk.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    keep = ("due", "sent", "times", "tokens", "done", "error")
    return {**hello, "requests": [{k: r[k] for k in keep} for r in reqs]}


def main(argv) -> int:
    plan_path, out_path = argv
    with open(plan_path) as f:
        plan = json.load(f)
    try:
        out = asyncio.run(run(plan))
    except RuntimeError as e:
        print(f"loadgen: {e}", file=sys.stderr, flush=True)
        return 1
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
