"""The open-loop load generator: stdlib only, one thread (asyncio).

``schedule`` is a pure function of ``(mix, seed, seconds, vocab)``. The
prompt lengths, output lengths and inter-arrival gaps are the quantiles of
the mix's lognormal and exponential distributions at ``(i + 0.5) / n``,
put in an order drawn from the MIX's own ``schedule_seed``: every run of a
cell sends the same requests at the same times. ``--seed`` draws the token
ids (and, in the child, the weights). Measured on the chip (PERF.md, PR
26): with the order drawn from ``--seed`` the same multiset gave a p90 time
to first token of 361 to 1,184 ms at 0.8 of the knee — the seed was
changing the work.

``run_window`` sends each request when it is DUE, whatever the system is
doing (open loop), streams the answer and stamps every token's arrival.
Latencies count from the due time, and how late the generator itself
sent is reported.
"""

from __future__ import annotations

import asyncio
import json
import math
import random
import time
from dataclasses import dataclass, field
from statistics import NormalDist


@dataclass
class Request:
    index: int
    due_s: float
    prompt: list
    max_new: int


@dataclass
class Record:
    index: int
    due_s: float
    prompt_len: int
    max_new: int
    sent_s: float = math.nan
    status: int = 0
    error: str = ""
    token_times: list = field(default_factory=list)  # one per token
    tokens: list = field(default_factory=list)       # streamed deltas
    final: dict | None = None
    done_s: float = math.nan

    @property
    def ok(self) -> bool:
        return (self.status == 200 and self.final is not None
                and len(self.tokens) == self.max_new
                and self.final["token_ids"][self.prompt_len:] == self.tokens)


def _lognormal_quantiles(spec: dict, n: int) -> list[int]:
    nd = NormalDist()
    mu = math.log(spec["median"])
    return [int(min(spec["max"], max(spec["min"], round(math.exp(
        mu + spec["sigma"] * nd.inv_cdf((i + 0.5) / n)))))) for i in range(n)]


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    rate = mix["rate_per_s"]
    n = max(1, round(rate * seconds))
    order = random.Random(int(mix["schedule_seed"]))
    rng = random.Random(int(seed))
    if mix["arrivals"] == "poisson":
        gaps = [-math.log(1 - (i + 0.5) / n) / rate for i in range(n)]
    elif mix["arrivals"] == "uniform":
        gaps = [1.0 / rate] * n
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    prompts = _lognormal_quantiles(mix["prompt_tokens"], n)
    outs = _lognormal_quantiles(mix["output_tokens"], n)
    for xs in (gaps, prompts, outs):
        order.shuffle(xs)
    # the last request falls due before the window closes
    scale = seconds / (sum(gaps) + 1.0 / rate)
    reqs, t = [], 0.0
    for i in range(n):
        t += gaps[i] * scale
        reqs.append(Request(i, t, [rng.randrange(1, vocab)
                                   for _ in range(prompts[i])], outs[i]))
    return reqs


def percentile(values: list, q: float) -> float:
    """Nearest rank: the smallest value with at least ``q`` of the
    sample at or below it."""
    xs = sorted(values)
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


# ------------------------------------------------------------- the client

async def _read_head(reader) -> tuple[int, dict]:
    head = await reader.readuntil(b"\r\n\r\n")
    lines = head.decode("latin1").split("\r\n")
    status = int(lines[0].split()[1])
    headers = {}
    for ln in lines[1:]:
        if ":" in ln:
            k, v = ln.split(":", 1)
            headers[k.strip().lower()] = v.strip()
    return status, headers


async def _body_chunks(reader, headers: dict):
    """Yield the body's bytes as they arrive (chunked or by length)."""
    if headers.get("transfer-encoding", "").lower() == "chunked":
        while True:
            size = int((await reader.readline()).split(b";")[0].strip(), 16)
            if size == 0:
                await reader.readline()
                return
            yield await reader.readexactly(size)
            await reader.readexactly(2)
    elif "content-length" in headers:
        yield await reader.readexactly(int(headers["content-length"]))
    else:
        yield await reader.read()


async def _one(host: str, port: int, req: Request, rec: Record, t0: float,
               on_token=None) -> None:
    body = json.dumps({"token_ids": req.prompt, "max_new_tokens": req.max_new,
                       "stream": True}).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\nConnection: close\r\n"
                     b"Content-Length: " + str(len(body)).encode()
                     + b"\r\n\r\n" + body)
        await writer.drain()
        rec.status, headers = await _read_head(reader)
        buf = b""
        async for chunk in _body_chunks(reader, headers):
            now = time.monotonic() - t0
            buf += chunk
            *lines, buf = buf.split(b"\n")
            for ln in lines:
                if not ln.strip():
                    continue
                doc = json.loads(ln)
                if "metrics" in doc or "finish_reason" in doc \
                        or "error" in doc or rec.status != 200:
                    rec.final = doc
                    continue
                new = doc.get("token_ids", [])
                if on_token is not None:
                    new = on_token(rec, new)
                rec.tokens.extend(new)
                rec.token_times.extend([now] * len(new))
        if buf.strip():
            rec.final = json.loads(buf)
    except Exception as e:  # noqa: BLE001 — a failed request is counted
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        rec.done_s = time.monotonic() - t0
        if writer is not None:
            writer.close()


async def get_json(host: str, port: int, path: str) -> dict:
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\nHost: bench\r\n"
                     "Connection: close\r\n\r\n".encode())
        await writer.drain()
        _, headers = await _read_head(reader)
        data = b""
        async for chunk in _body_chunks(reader, headers):
            data += chunk
        return json.loads(data)
    finally:
        writer.close()


async def run_window(host: str, port: int, reqs: list, seconds: float, *,
                     timed: list | None = None, poll_s: float = 1.0,
                     wait_s: float = 60.0, on_token=None) -> dict:
    """Drive one window. ``timed`` is ``[(at_s, callable), ...]`` run
    from this loop (the trace's start and stop). After the close every
    request in flight is waited for, ``wait_s`` at the most. Returns the
    records, the ``/stats`` before and after the window and its polls."""
    records = [Record(r.index, r.due_s, len(r.prompt), r.max_new)
               for r in reqs]
    before = await get_json(host, port, "/stats")
    t0 = time.monotonic()
    polls: list = []
    marks: dict = {}

    async def send(req, rec):
        await asyncio.sleep(max(0.0, req.due_s - (time.monotonic() - t0)))
        rec.sent_s = time.monotonic() - t0
        await _one(host, port, req, rec, t0, on_token)

    async def poll():
        while True:
            await asyncio.sleep(poll_s)
            try:
                polls.append((time.monotonic() - t0,
                              await get_json(host, port, "/stats")))
            except Exception:  # noqa: BLE001 — a missed poll is no result
                pass

    async def at(when, name, fn):
        await asyncio.sleep(max(0.0, when - (time.monotonic() - t0)))
        marks[name + "_s"] = time.monotonic() - t0
        await asyncio.get_running_loop().run_in_executor(None, fn)
        marks[name + "_done_s"] = time.monotonic() - t0

    tasks = [asyncio.ensure_future(send(q, r)) for q, r in zip(reqs, records)]
    side = [asyncio.ensure_future(poll())] + [
        asyncio.ensure_future(at(w, n, f)) for w, n, f in (timed or [])]
    await asyncio.sleep(max(0.0, seconds - (time.monotonic() - t0)))
    closed_s = time.monotonic() - t0
    at_close = await get_json(host, port, "/stats")
    _, pending = await asyncio.wait(tasks, timeout=wait_s)
    for t in pending:
        t.cancel()
    for t in side[1:]:
        if not t.done():
            t.cancel()
    side[0].cancel()
    await asyncio.gather(*pending, *side, return_exceptions=True)
    after = await get_json(host, port, "/stats")
    return {"records": records, "stats_before": before,
            "stats_at_close": at_close, "stats_after": after,
            "polls": polls, "closed_s": closed_s, "marks": marks,
            "end_s": time.monotonic() - t0, "unfinished": len(pending)}


# ---------------------------------------------------------- the reduction

def summarise(win: dict, seconds: float) -> dict:
    """End-to-end numbers of a window, over ALL its requests."""
    recs = win["records"]
    end = win["end_s"]
    failed = [r for r in recs if not r.ok]
    # a failed or refused request waited for the whole run: the worst
    ttft = [(r.token_times[0] - r.due_s) if r.ok else (end - r.due_s)
            for r in recs]
    gaps = [b - a for r in recs if r.ok
            for a, b in zip(r.token_times, r.token_times[1:])]
    late = [r.sent_s - r.due_s for r in recs if not math.isnan(r.sent_s)]
    in_window = [r for r in recs if r.ok and r.done_s <= win["closed_s"]]

    def backlog(t):   # due by t and not yet answered
        return sum(1 for r in recs if r.due_s <= t
                   and not (r.done_s <= t))
    return {
        "attempted": len(recs), "failed": len(failed),
        "failures": [f"#{r.index}: status {r.status} {r.error} "
                     f"{len(r.tokens)}/{r.max_new} tokens"
                     for r in failed[:5]],
        "ttft_p90_ms": 1e3 * percentile(ttft, 0.90),
        "ttft_p50_ms": 1e3 * percentile(ttft, 0.50),
        "itl_p95_ms": 1e3 * percentile(gaps, 0.95) if gaps else math.nan,
        "itl_p50_ms": 1e3 * percentile(gaps, 0.50) if gaps else math.nan,
        "token_gaps": len(gaps),
        # every output token that reached the client inside the window
        # (requests still in flight at the close have worked in it too:
        # counting only finished requests swings by one long answer)
        "serve_tokens_per_s": sum(
            1 for r in recs for t in r.token_times if t <= win["closed_s"])
        / win["closed_s"],
        "completed_tokens_per_s": sum(len(r.tokens) for r in in_window)
        / win["closed_s"],
        "completed_in_window": len(in_window),
        "loadgen_late_p95_ms": 1e3 * percentile(late, 0.95),
        "loadgen_late_max_ms": 1e3 * max(late),
        "drain_after_close_s": end - win["closed_s"],
        "backlog_mid": backlog(win["closed_s"] / 2),
        "backlog_end": backlog(win["closed_s"]),
    }


def span_work(recs: list, t_lo: float, t_hi: float) -> dict:
    """What the client saw happen inside [t_lo, t_hi): output tokens that
    arrived, prompts whose first token arrived (their prefill ran just
    before), and the time-average of the positions live in the batch."""
    out_tokens, prompts, live_area = 0, [], 0.0
    positions = []   # (position, sampled) of every token the model fed
    for r in recs:
        if not r.token_times:
            continue
        for j, t in enumerate(r.token_times):
            if t_lo <= t < t_hi:
                out_tokens += 1
                if j == 0:
                    prompts.append(r.prompt_len)
                    positions += [(p, p == r.prompt_len - 1)
                                  for p in range(r.prompt_len)]
                else:
                    positions.append((r.prompt_len + j - 1, True))
        # while decoding, the request holds prompt + tokens-so-far
        ts = r.token_times
        for j in range(len(ts) - 1):
            lo, hi = max(ts[j], t_lo), min(ts[j + 1], t_hi)
            if hi > lo:
                live_area += (hi - lo) * (r.prompt_len + j + 1)
    return {"out_tokens": out_tokens, "prompt_tokens": sum(prompts),
            "prefills": len(prompts), "positions": positions,
            "live_tokens_mean": live_area / max(t_hi - t_lo, 1e-9)}


# ------------------------------------------- a blocking client (warm-up)

def generate_blocking(url: str, prompt: list, n_new: int,
                      timeout: float = 900.0) -> list:
    """One streamed ``POST /v1/generate`` with urllib; returns the new
    tokens. Used by the child to warm every shape before the window."""
    import urllib.request

    req = urllib.request.Request(url + "/v1/generate", data=json.dumps({
        "token_ids": prompt, "max_new_tokens": n_new,
        "stream": True}).encode())
    with urllib.request.urlopen(req, timeout=timeout) as r:
        lines = [json.loads(ln) for ln in r.read().decode().splitlines()
                 if ln.strip()]
    new = lines[-1]["token_ids"][len(prompt):]
    if len(new) != n_new:
        raise RuntimeError(f"warm-up asked {n_new} tokens, got {len(new)}: "
                           f"{lines[-1].get('finish_reason')}")
    return new
