"""Process actor backend: actor hosted in a spawned child process.

Unlike the reference's lock-step pipe protocol (one in-flight request,
ref: ``byzpy/engine/actor/backends/process.py:111-321`` with its ``_io_lock``
pipe-race note), this backend tags every frame with a request id and runs an
asyncio loop in the child, so multiple requests (e.g. a blocking ``chan_get``
plus a ``call``) are in flight concurrently without deadlock.

Useful on TPU hosts for CPU-side work (data loading, combinatorial subset
enumeration) that must not block the device-driving process. Payloads cross
the pipe as cloudpickle frames with device arrays converted to numpy
(``wire.host_view``) — tensors never move between chips this way.
"""

from __future__ import annotations

import asyncio
import inspect
import itertools
import multiprocessing as mp
import os
import traceback
import uuid
from typing import Any, Dict, Optional

import cloudpickle

from ....utils.platform import child_env
from .. import ipc, wire
from ..channels import Endpoint
from ..router import channel_router

_counter = itertools.count()

# BYZPY_TPU_SHM=0 forces all payloads inline through the pipe
_SHM_ENABLED = os.environ.get("BYZPY_TPU_SHM", "1") != "0"


# ---------------------------------------------------------------------------
# Child-process side
# ---------------------------------------------------------------------------


def _worker_main(conn) -> None:  # pragma: no cover - runs in child process
    asyncio.run(_worker_loop(conn))


async def _worker_loop(conn) -> None:  # pragma: no cover - runs in child process
    loop = asyncio.get_running_loop()
    obj_holder: Dict[str, Any] = {}
    mailboxes: Dict[str, asyncio.Queue] = {}
    send_lock = asyncio.Lock()
    stopping = asyncio.Event()

    async def reply(req_id: int, ok: bool, payload: Any) -> None:
        blob = cloudpickle.dumps((req_id, ok, payload))
        async with send_lock:
            await loop.run_in_executor(None, conn.send_bytes, blob)

    async def handle(req_id: int, op: str, data: Any) -> None:
        try:
            if op == "construct":
                target, args, kwargs = data
                args, kwargs = ipc.unwrap_payload((args, kwargs), copy=True, close=True)
                obj_holder["obj"] = target(*args, **kwargs)
                result = None
            elif op == "call":
                method, args, kwargs = data
                args, kwargs = ipc.unwrap_payload((args, kwargs), copy=True, close=True)
                obj = obj_holder.get("obj")
                if obj is None:
                    raise RuntimeError("actor not constructed")
                fn = getattr(obj, method)
                result = fn(*args, **kwargs)
                if inspect.isawaitable(result):
                    result = await result
                result = wire.host_view(result)
            elif op == "chan_open":
                mailboxes.setdefault(data, asyncio.Queue())
                result = None
            elif op == "chan_put":
                name, payload = data
                # copy shm payloads out now: the sender unlinks its segments
                # as soon as this request is acknowledged, and the mailbox
                # may be drained much later
                payload = ipc.unwrap_payload(payload, copy=True, close=True)
                await mailboxes.setdefault(name, asyncio.Queue()).put(payload)
                result = None
            elif op == "chan_get":
                result = await mailboxes.setdefault(data, asyncio.Queue()).get()
            elif op == "stop":
                stopping.set()
                result = None
            else:
                raise ValueError(f"unknown op {op!r}")
            await reply(req_id, True, result)
        except BaseException as exc:  # noqa: BLE001 - report to parent
            await reply(req_id, False, (type(exc).__name__, str(exc), traceback.format_exc()))

    async def read_frames() -> None:
        while not stopping.is_set():
            try:
                blob = await loop.run_in_executor(None, conn.recv_bytes)
            except (EOFError, OSError):
                break
            req_id, op, data = cloudpickle.loads(blob)
            asyncio.ensure_future(handle(req_id, op, data))

    reader = asyncio.ensure_future(read_frames())
    await stopping.wait()
    reader.cancel()


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------


class ProcessActorBackend:
    """Subprocess backend: one spawned process per actor, cloudpickle frames over a pipe with request-id correlation."""
    scheme = "process"

    def __init__(
        self, *, actor_id: str | None = None, child_platform: str = "cpu"
    ) -> None:
        self.actor_id = actor_id or f"proc-{next(_counter)}-{uuid.uuid4().hex[:6]}"
        self._child_platform = (
            os.environ.get("BYZPY_TPU_CHILD_PLATFORM") or child_platform
        )
        self._proc: mp.process.BaseProcess | None = None
        self._conn = None
        self._reader_task: asyncio.Task | None = None
        self._pending: Dict[int, asyncio.Future] = {}
        self._req_ids = itertools.count()
        self._send_lock: asyncio.Lock | None = None
        self._started = False

    async def start(self) -> None:
        if self._started:
            return
        ctx = mp.get_context("spawn")
        parent_conn, child_conn = ctx.Pipe(duplex=True)
        self._proc = ctx.Process(target=_worker_main, args=(child_conn,), daemon=True)
        patch = child_env(self._child_platform)
        saved = {k: os.environ.get(k) for k in patch}
        os.environ.update(patch)
        try:
            self._proc.start()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        child_conn.close()
        self._conn = parent_conn
        self._send_lock = asyncio.Lock()
        self._reader_task = asyncio.ensure_future(self._read_replies())
        channel_router.register(self.get_endpoint(), self)
        self._started = True

    async def _read_replies(self) -> None:
        loop = asyncio.get_running_loop()
        try:
            while True:
                blob = await loop.run_in_executor(None, self._conn.recv_bytes)
                req_id, ok, payload = cloudpickle.loads(blob)
                fut = self._pending.pop(req_id, None)
                if fut is None or fut.done():
                    continue
                if ok:
                    fut.set_result(payload)
                else:
                    name, msg, tb = payload
                    fut.set_exception(RuntimeError(f"{name} in actor process: {msg}\n{tb}"))
        except asyncio.CancelledError:
            raise
        except BaseException as exc:  # noqa: BLE001 - fail pending, don't hang them
            err = exc if not isinstance(exc, (EOFError, OSError)) else None
            for fut in self._pending.values():
                if not fut.done():
                    fut.set_exception(
                        ConnectionError(f"actor process pipe closed{f': {err!r}' if err else ''}")
                    )
            self._pending.clear()

    async def _request(self, op: str, data: Any) -> Any:
        self._ensure_started()
        if self._reader_task is not None and self._reader_task.done():
            raise ConnectionError("actor process pipe closed (reader exited)")
        req_id = next(self._req_ids)
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._pending[req_id] = fut
        blob = cloudpickle.dumps((req_id, op, data))
        loop = asyncio.get_running_loop()
        async with self._send_lock:
            await loop.run_in_executor(None, self._conn.send_bytes, blob)
        return await fut

    async def construct(self, target: Any, /, *args: Any, **kwargs: Any) -> None:
        await self._shm_request("construct", target, args, kwargs)

    async def call(self, method: str, /, *args: Any, **kwargs: Any) -> Any:
        return await self._shm_request("call", method, args, kwargs)

    async def _shm_request(self, op: str, head: Any, args: Any, kwargs: Any) -> Any:
        """Ship large host arrays via the native shm store instead of the
        pipe (ref: the reference's wrap_payload on every process hop,
        ``byzpy/engine/actor/ipc.py:20-42``); the child copies out and
        unmaps, the parent unlinks after the reply."""
        payload = wire.host_view((args, kwargs))
        if _SHM_ENABLED:
            payload, handles = ipc.wrap_payload(payload)
        else:
            handles = []
        try:
            return await self._request(op, (head, payload[0], payload[1]))
        finally:
            ipc.cleanup_handles(handles)

    async def close(self) -> None:
        if not self._started:
            return
        channel_router.unregister(self.get_endpoint())
        try:
            await asyncio.wait_for(self._request("stop", None), timeout=5)
        except Exception:
            pass
        if self._reader_task is not None:
            self._reader_task.cancel()
        if self._conn is not None:
            # EOF lets the child's blocked conn.recv_bytes thread exit so the
            # child terminates promptly instead of riding out join+kill.
            self._conn.close()
        # snapshot-and-null BEFORE awaiting: the off-loop join suspends
        # this coroutine, and a concurrent close() must not re-enter the
        # join/kill sequence or dereference a nulled _proc
        proc, self._proc = self._proc, None
        self._conn = None
        self._started = False
        if proc is not None:
            # join() blocks up to its timeout: run it off-loop so a slow
            # child cannot stall every other actor sharing this event loop
            # (same pattern as node/process_context.py shutdown)
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, proc.join, 5)
            if proc.is_alive():
                proc.kill()
                await loop.run_in_executor(None, proc.join, 5)

    def get_endpoint(self) -> Endpoint:
        return Endpoint(self.scheme, "local", self.actor_id)

    async def chan_open(self, name: str) -> None:
        await self._request("chan_open", name)

    async def deliver_local(self, name: str, payload: Any) -> None:
        hosted = wire.host_view(payload)
        if _SHM_ENABLED:
            wrapped, handles = ipc.wrap_payload(hosted)
        else:
            wrapped, handles = hosted, []
        try:
            await self._request("chan_put", (name, wrapped))
        finally:
            ipc.cleanup_handles(handles)

    async def chan_put(
        self, name: str, payload: Any, *, endpoint: Optional[Endpoint] = None
    ) -> None:
        if endpoint is None or endpoint == self.get_endpoint():
            await self.deliver_local(name, payload)
            return
        if await channel_router.deliver(endpoint, name, payload):
            return
        if endpoint.scheme == "tcp":
            from ..transports import tcp

            await tcp.chan_put(endpoint, name, payload)
            return
        raise LookupError(f"no route to endpoint {endpoint}")

    async def chan_get(self, name: str) -> Any:
        return await self._request("chan_get", name)

    def _ensure_started(self) -> None:
        if not self._started:
            raise RuntimeError("backend not started; call start() first")


__all__ = ["ProcessActorBackend"]
