"""ProcessContext: run a DecentralizedNode inside a spawned child process.

Behavior parity: ``byzpy/engine/node/context.py:126-490`` — the node is
rebuilt in the child from a cloudpickled ``configure`` callable, commands
(``stop`` / ``execute_pipeline``) travel a cmd queue, messages travel
inbox/outbox ``mp.Queue``s, and the parent routes child→child frames
between sibling contexts (and to in-process nodes via the shared delivery
table).

TPU note: a subprocess gets its own XLA client. Children default to the
**CPU** platform (``BYZPY_TPU_CHILD_PLATFORM`` overrides) because a TPU
chip admits one process at a time — the idiomatic TPU deployment keeps
device compute in the parent (or uses the SPMD paths in
``byzpy_tpu.parallel``) and uses process nodes for host-side work,
matching the reference's use of process actors for data loading.
"""

from __future__ import annotations

import asyncio
import multiprocessing as mp
import os
import uuid
from typing import Any, Callable, ClassVar, Dict, Mapping, Optional

import cloudpickle

from ...utils.platform import child_env
from ..actor.wire import host_view
from .context import Message, NodeContext, register_delivery_route, route_message

Configure = Callable[[Any], None]  # (DecentralizedNode) -> None, picklable


def _child_main(node_id: str, blob: bytes, inbox_q, outbox_q, cmd_q, result_q,
                platform: str) -> None:
    if platform:
        os.environ["JAX_PLATFORMS"] = platform
    asyncio.run(_child_async(node_id, blob, inbox_q, outbox_q, cmd_q, result_q))


async def _child_async(node_id, blob, inbox_q, outbox_q, cmd_q, result_q) -> None:
    from .decentralized import DecentralizedNode

    configure, topology, node_ids = cloudpickle.loads(blob)

    class _Bridge(NodeContext):
        """Child-side context: sends hop through the parent router."""

        def __init__(self) -> None:
            self.node_id = node_id
            self._node = None

        async def start(self, node) -> None:
            self._node = node

        async def send_message(self, target_id: str, message: Message) -> None:
            outbox_q.put(("send", target_id, host_view(message)))

        async def shutdown(self) -> None:
            pass

    bridge = _Bridge()
    node = DecentralizedNode(node_id, bridge)
    if topology is not None and node_ids is not None:
        node.bind_topology(topology, node_ids)
    if configure is not None:
        configure(node)
    await node.start()

    import logging
    import queue as _queue

    log = logging.getLogger(__name__)

    async def _run_pipeline(req_id: str, name: str, inputs) -> None:
        try:
            result = await node.execute_pipeline(name, inputs)
            result_q.put((req_id, "ok", host_view(result)))
        except Exception as exc:  # noqa: BLE001 — report to parent
            result_q.put((req_id, "error", repr(exc)))

    pipeline_tasks: list[asyncio.Task] = []
    running = True
    while running:
        progressed = False
        try:
            msg = inbox_q.get_nowait()
        except _queue.Empty:
            msg = None
        except Exception:  # noqa: BLE001 — a frame that fails to unpickle
            log.exception("node %s: dropping undecodable inbox frame", node_id)
            msg = None
            progressed = True
        if msg is not None:
            progressed = True
            await node.handle_incoming_message(msg)
        try:
            cmd = cmd_q.get_nowait()
            progressed = True
        except _queue.Empty:
            cmd = None
        if cmd is not None:
            if cmd[0] == "stop":
                running = False
            elif cmd[0] == "execute_pipeline":
                _, req_id, name, inputs = cmd
                # run as a background task so the inbox keeps draining —
                # pipelines may block on wait_for_message for traffic that
                # still has to flow through this loop
                pipeline_tasks.append(
                    asyncio.ensure_future(_run_pipeline(req_id, name, inputs))
                )
        pipeline_tasks = [t for t in pipeline_tasks if not t.done()]
        if not progressed:
            # reference polls its queues at 1ms (ref: context.py:319-490);
            # same cadence, but non-blocking so the loop stays responsive
            await asyncio.sleep(0.001)
    for task in pipeline_tasks:
        task.cancel()
    for task in pipeline_tasks:
        try:
            await task
        except (asyncio.CancelledError, Exception):  # noqa: BLE001
            pass
    await node.shutdown()
    result_q.put((None, "stopped", None))


class ProcessContext(NodeContext):
    """Parent-side handle for a node hosted in a child process."""

    _registry: ClassVar[Dict[str, "ProcessContext"]] = {}
    _route_registered: ClassVar[bool] = False

    def __init__(
        self,
        node_id: str,
        configure: Optional[Configure] = None,
        *,
        child_platform: str = "cpu",
    ) -> None:
        self.node_id = node_id
        self._configure = configure
        self._platform = (
            os.environ.get("BYZPY_TPU_CHILD_PLATFORM") or child_platform
        )
        ctx = mp.get_context("spawn")
        self._inbox = ctx.Queue()
        self._outbox = ctx.Queue()
        self._cmd = ctx.Queue()
        self._result = ctx.Queue()
        self._ctx = ctx
        self._proc: Optional[mp.Process] = None
        self._pump_task: Optional[asyncio.Task] = None
        self._drain_task: Optional[asyncio.Task] = None
        self._pending: Dict[str, asyncio.Future] = {}
        self._closing = False

    @classmethod
    def clear_registry(cls) -> None:
        cls._registry.clear()

    def set_configure(self, configure: Configure) -> None:
        """Install (or replace) the child-side configure hook. Public
        contract for orchestrators that must register pipelines where the
        node state actually lives (the child process) — e.g. the P2P
        runner. Must be called before :meth:`start`."""
        if self._proc is not None:
            raise RuntimeError("cannot set configure hook after start()")
        self._configure = configure

    async def start(self, node) -> None:
        if self.node_id in self._registry:
            raise RuntimeError(f"node id {self.node_id!r} already registered")
        if not ProcessContext._route_registered:
            register_delivery_route(_process_route)
            ProcessContext._route_registered = True
        router = node._router  # may be None when no topology is bound
        topology = router.topology if router is not None else None
        node_ids = router.node_ids if router is not None else None
        blob = cloudpickle.dumps((self._configure, topology, node_ids))
        self._proc = self._ctx.Process(
            target=_child_main,
            args=(self.node_id, blob, self._inbox, self._outbox, self._cmd,
                  self._result, self._platform),
            daemon=True,
        )
        # for the duration of the spawn only
        patch = child_env(self._platform)
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
        self._registry[self.node_id] = self
        self._pump_task = asyncio.ensure_future(self._pump())
        self._drain_task = asyncio.ensure_future(self._drain_results())

    async def _pump(self) -> None:
        """Route child→child frames and resolve pipeline futures."""
        loop = asyncio.get_running_loop()
        while True:
            frame = await loop.run_in_executor(None, self._queue_get, self._outbox)
            if frame is None:
                break
            kind = frame[0]
            if kind == "send":
                _, target_id, message = frame
                target = self._registry.get(target_id)
                if target is not None:
                    target._inbox.put(message)
                elif not await route_message(target_id, message):
                    import logging

                    logging.getLogger(__name__).warning(
                        "process node %s -> unknown target %s",
                        self.node_id, target_id,
                    )

    def _queue_get(self, q):
        """Blocking queue read that returns None once the child is gone (or
        shutdown began), so the executor thread exits and the loop can
        close."""
        while True:
            if self._closing or (
                self._proc is not None and not self._proc.is_alive()
            ):
                return None
            try:
                return q.get(timeout=0.2)
            except Exception:
                continue

    async def _drain_results(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            frame = await loop.run_in_executor(None, self._queue_get, self._result)
            if frame is None:
                break
            req_id, status, payload = frame
            fut = self._pending.pop(req_id, None)
            if fut is None or fut.done():
                continue
            if status == "ok":
                fut.set_result(payload)
            else:
                fut.set_exception(RuntimeError(f"pipeline failed: {payload}"))
        # child is gone (or shutdown began): nothing will resolve what's left
        for fut in self._pending.values():
            if not fut.done():
                fut.set_exception(
                    ConnectionError(f"node {self.node_id!r} is no longer running")
                )
        self._pending.clear()

    async def remote_execute_pipeline(
        self, name: str, inputs: Mapping[str, Any]
    ) -> Any:
        """Proxy ``execute_pipeline`` into the child (DecentralizedNode
        detects this method and delegates)."""
        if self._proc is None or not self._proc.is_alive():
            raise ConnectionError(f"node {self.node_id!r} is not running")
        req_id = uuid.uuid4().hex
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._pending[req_id] = fut
        self._cmd.put(("execute_pipeline", req_id, name, host_view(dict(inputs))))
        return await fut

    async def send_message(self, target_id: str, message: Message) -> None:
        target = self._registry.get(target_id)
        if target is not None:
            target._inbox.put(host_view(message))
            return
        if not await route_message(target_id, host_view(message)):
            raise ConnectionError(f"node {target_id!r} is not running")

    async def shutdown(self) -> None:
        self._registry.pop(self.node_id, None)
        self._closing = True
        if self._proc is not None:
            self._cmd.put(("stop",))
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, self._proc.join, 5)
            if self._proc.is_alive():
                self._proc.terminate()
                await loop.run_in_executor(None, self._proc.join, 5)
        # the pump/drain executor threads notice _closing within 0.2s and
        # return; await the tasks so no thread outlives the loop
        for attr in ("_pump_task", "_drain_task"):
            task = getattr(self, attr)
            if task is not None:
                try:
                    await task
                except Exception:  # noqa: BLE001
                    pass
                setattr(self, attr, None)
        self._proc = None
        for fut in self._pending.values():
            if not fut.done():
                fut.set_exception(ConnectionError("node shut down"))
        self._pending.clear()


async def _process_route(target_id: str, message: Message) -> bool:
    target = ProcessContext._registry.get(target_id)
    if target is None:
        return False
    target._inbox.put(host_view(message))
    return True


__all__ = ["ProcessContext"]
