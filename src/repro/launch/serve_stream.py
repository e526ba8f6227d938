"""Streaming serving front-end: newline-delimited JSON over a TCP socket.

Runs the wall-clock :class:`~repro.serving.streaming.AsyncEngine` behind an
asyncio socket server, so request-shaping / tokenization / client I/O live
in OTHER processes and the dispatch loop's process does nothing but step
the engine and shuttle small JSON lines (the aphrodite/vLLM
multiprocessing-front-end split).

    PYTHONPATH=src python -m repro.launch.serve_stream --arch qwen2-1.5b \
        --port 8765 --batch 4 --k 5 --max-new 32

Protocol — one JSON object per line, both directions:

client → server::

    {"op": "generate", "id": "r1", "prompt": [3, 17, ...],
     "max_new_tokens": 32,            # optional
     "temperature": 0.8, "top_k": 0, "top_p": 1.0, "seed": 7}  # optional
    {"op": "abort", "id": "r1"}
    {"op": "health"}

server → client::

    {"id": "r1", "event": "tokens", "tokens": [..], "logprobs": [..]}
    {"id": "r1", "event": "done", "n_new": 12, "aborted": false}
    {"event": "health", "queue_depth": 0, ...}
    {"id": "r1", "event": "error", "message": "..."}

``tokens`` events carry everything one speculative sync committed for the
request (already stop/budget-trimmed — the stream never shows a token past
the stop). ``id`` is the client's correlation key, scoped per connection.
A dropped connection aborts its in-flight requests, freeing their slots.

Demo client (same protocol, for smoke tests and as reference code)::

    PYTHONPATH=src python -m repro.launch.serve_stream --client \
        --port 8765 --requests 4
"""
from __future__ import annotations

import argparse
import asyncio
import json
from typing import Any, Dict, Optional

import numpy as np

from repro.serving.sampling import SamplingParams
from repro.serving.streaming import AsyncEngine, StreamHandle


def _sampling_from(msg: Dict[str, Any]) -> Optional[SamplingParams]:
    """Build the request's SamplingParams from protocol fields (None when
    the message sets no policy field — engine default applies)."""
    keys = ("temperature", "top_k", "top_p", "seed", "stop_token_ids")
    if not any(k in msg for k in keys):
        return None
    return SamplingParams(temperature=float(msg.get("temperature", 0.0)),
                          top_k=int(msg.get("top_k", 0)),
                          top_p=float(msg.get("top_p", 1.0)),
                          seed=int(msg.get("seed", 0)),
                          stop_token_ids=tuple(msg.get("stop_token_ids", ())))


class _Connection:
    """One client connection: reads NDJSON ops, fans generate ops out to
    per-request pump tasks, serializes writes through a lock so concurrent
    streams never interleave mid-line."""

    def __init__(self, aeng: AsyncEngine, reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter):
        self.aeng = aeng
        self.reader = reader
        self.writer = writer
        self.wlock = asyncio.Lock()
        self.handles: Dict[str, StreamHandle] = {}
        self.tasks: Dict[str, asyncio.Task] = {}

    async def send(self, obj: Dict[str, Any]) -> None:
        line = (json.dumps(obj) + "\n").encode()
        async with self.wlock:
            self.writer.write(line)
            # drain under the lock: a slow client socket backpressures its
            # own connection task, never the engine's dispatch loop
            await self.writer.drain()

    async def _pump(self, cid: str, handle: StreamHandle) -> None:
        """Forward one request's committed tokens to the client as they
        stream out of the engine, then the done event."""
        try:
            try:
                async for tok, lp in handle:
                    toks, lps = [tok], [lp]
                    # batch whatever the same sync already delivered
                    while True:
                        try:
                            nxt = handle._queue.get_nowait()
                        except asyncio.QueueEmpty:
                            break
                        if nxt is None or isinstance(nxt, BaseException):
                            handle._queue.put_nowait(nxt)
                            break
                        toks.append(nxt[0])
                        lps.append(nxt[1])
                    await self.send({"id": cid, "event": "tokens",
                                     "tokens": toks, "logprobs": lps})
                final = {"id": cid, "event": "done",
                         "n_new": len(handle.request.out_tokens),
                         "aborted": handle.aborted}
            except Exception as e:                   # engine failure
                final = {"id": cid, "event": "error", "message": repr(e)}
            try:
                await self.send(final)
            except (ConnectionError, RuntimeError):
                pass                                 # client vanished
        finally:
            self.handles.pop(cid, None)
            self.tasks.pop(cid, None)

    async def handle_op(self, msg: Dict[str, Any]) -> None:
        op = msg.get("op")
        if op == "generate":
            cid = str(msg.get("id"))
            if cid in self.handles:
                await self.send({"id": cid, "event": "error",
                                 "message": "duplicate id"})
                return
            try:
                prompt = np.asarray(msg["prompt"], np.int32)
                handle = await self.aeng.submit(
                    prompt, sampling_params=_sampling_from(msg),
                    max_new_tokens=msg.get("max_new_tokens"))
            except (ValueError, KeyError, TypeError) as e:
                await self.send({"id": cid, "event": "error",
                                 "message": str(e)})
                return
            self.handles[cid] = handle
            self.tasks[cid] = asyncio.get_running_loop().create_task(
                self._pump(cid, handle))
        elif op == "abort":
            cid = str(msg.get("id"))
            handle = self.handles.get(cid)
            # the pump task sees the finish sentinel and sends "done"
            ok = handle.abort() if handle is not None else False
            if not ok and handle is None:
                await self.send({"id": cid, "event": "error",
                                 "message": "unknown id"})
        elif op == "health":
            await self.send({"event": "health", **self.aeng.health()})
        else:
            await self.send({"event": "error",
                             "message": f"unknown op {op!r}"})

    async def run(self) -> None:
        try:
            while True:
                line = await self.reader.readline()
                if not line:
                    break
                try:
                    msg = json.loads(line)
                except json.JSONDecodeError as e:
                    await self.send({"event": "error", "message": str(e)})
                    continue
                await self.handle_op(msg)
        except (OSError, RuntimeError):
            # a reset mid-read (ECONNRESET surfaces through readline) or a
            # send() on the closed transport: same as EOF — fall through to
            # the cleanup below instead of killing the task with an
            # unretrieved exception
            pass
        finally:
            # a vanished client must not pin slots/pages
            for handle in list(self.handles.values()):
                if not handle.done:
                    handle.abort()
            for t in list(self.tasks.values()):
                t.cancel()
            self.writer.close()


async def start_stream_server(aeng: AsyncEngine, host: str = "127.0.0.1",
                              port: int = 0) -> "asyncio.base_events.Server":
    """Start the NDJSON front-end for a (started or not) AsyncEngine;
    returns the asyncio Server (its sockets carry the bound port). Tests
    drive this in-process with port=0."""
    await aeng.start()

    async def on_client(reader, writer):
        await _Connection(aeng, reader, writer).run()

    return await asyncio.start_server(on_client, host, port)


# ---------------------------------------------------------------------------
# reference client (also the smoke test)
# ---------------------------------------------------------------------------
async def _demo_client(host: str, port: int, n_requests: int,
                       max_new: int, vocab: int, temperature: float,
                       seed: int) -> None:
    reader, writer = await asyncio.open_connection(host, port)
    rng = np.random.default_rng(seed)
    for i in range(n_requests):
        req = {"op": "generate", "id": f"r{i}",
               "prompt": rng.integers(0, vocab,
                                      size=int(rng.integers(4, 13))).tolist(),
               "max_new_tokens": max_new}
        if temperature > 0:
            req.update(temperature=temperature, seed=seed + i)
        writer.write((json.dumps(req) + "\n").encode())
    writer.write((json.dumps({"op": "health"}) + "\n").encode())
    await writer.drain()
    got: Dict[str, list] = {}
    done = 0
    while done < n_requests:
        msg = json.loads(await reader.readline())
        if msg.get("event") == "tokens":
            got.setdefault(msg["id"], []).extend(msg["tokens"])
        elif msg.get("event") == "done":
            done += 1
            print(f"{msg['id']}: {msg['n_new']} tokens"
                  + (" (aborted)" if msg["aborted"] else ""))
        elif msg.get("event") == "health":
            print("health:", {k: msg[k] for k in
                              ("queue_depth", "running", "pool_occupancy")})
        elif msg.get("event") == "error":
            print("error:", msg["message"])
            done += 1
    writer.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8765)
    ap.add_argument("--mode", default="parallel",
                    choices=["parallel", "ar", "none"])
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="page-pool size; 0 = batch * max_len/page_size")
    ap.add_argument("--prefix-cache", action="store_true")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="backpressure bound on in-flight requests "
                         "(0 = 4 * batch)")
    ap.add_argument("--ckpt", default="results/ckpt")
    ap.add_argument("--client", action="store_true",
                    help="run the reference NDJSON client instead of the "
                         "server (connects to --host/--port)")
    ap.add_argument("--requests", type=int, default=4,
                    help="(client) number of streamed requests")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="(client) per-request sampling temperature")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.client:
        asyncio.run(_demo_client(args.host, args.port, args.requests,
                                 args.max_new, 128, args.temperature,
                                 args.seed))
        return

    # heavyweight imports only on the server path — the client stays light
    from repro.launch.build import build_engine, init_target, \
        use_compile_cache
    from repro.serving import EngineConfig

    use_compile_cache()
    tcfg, _, tparams = init_target(args.arch, reduced=args.reduced)
    eng = build_engine(
        tcfg, tparams,
        EngineConfig(K=args.k, max_new_tokens=args.max_new,
                     drafter_mode=args.mode, max_len=args.max_len,
                     kv_layout="paged", page_size=args.page_size,
                     pool_pages=args.pool_pages,
                     prefix_cache=args.prefix_cache),
        args.batch, layers=args.layers, ckpt=args.ckpt)
    aeng = AsyncEngine(eng, eos_id=args.eos_id,
                       max_pending=args.max_pending or None)

    async def serve_forever():
        server = await start_stream_server(aeng, args.host, args.port)
        addr = server.sockets[0].getsockname()
        print(f"streaming NDJSON server on {addr[0]}:{addr[1]} "
              f"(batch={args.batch}, K={args.k}, mode={args.mode}, "
              f"max_pending={aeng.max_pending})")
        async with server:
            await server.serve_forever()

    try:
        asyncio.run(serve_forever())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
