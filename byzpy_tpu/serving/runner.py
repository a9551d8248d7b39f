"""Process-per-shard runner: real multi-core sharded serving.

PR 12 built the sharded tier in-process and PR 13's blame table sized
its limit: at 4 shards the root ``fold_merge`` is the largest single
critical-path entry (14.4% → 37.5% of round wall-clock at 1→4 shards),
and the whole scale lane still *modeled* the makespan on one core —
``ShardedCoordinator`` owned every shard object, nothing spawned
processes or drove the barrier over sockets. This module is the real
thing, in the actor-vs-learner process-split lineage of Podracer
(arXiv:2104.06272) and the MPMD program-partitioning stance of
arXiv:2412.14374:

* **one OS process per ingress shard** — each child hosts a full
  :class:`~byzpy_tpu.serving.sharded.ShardFrontend` admission plane
  (bounded queue, credits, staleness, ``(client, seq)`` dedup,
  forensics trust gating, write-ahead durability) behind its own TCP
  ingress speaking the existing HMAC/quantized actor wire. The runner
  control plane (``shard_close``/``confirm``/``requeue``/…) mounts on
  the SAME port through ``ServingFrontend.request_hook`` — one socket
  per shard serves submissions, Prometheus scrapes, and round control;
* **optional merge-node processes** — the depth-N merge tree
  (:class:`~byzpy_tpu.serving.sharded.MergeTopology`): a rack/pod-level
  node fans ``shard_close`` to its children, verifies each child frame
  (digest recompute + per-row home-shard ownership), and ships ONE
  combined :class:`~byzpy_tpu.serving.sharded.PartialFold` up
  (:func:`~byzpy_tpu.serving.sharded.combine_partials`) — the
  verification + concatenation + extras work that used to serialize on
  the root's critical path runs level-parallel across processes;
* **a root coordinator process** — a
  :class:`~byzpy_tpu.serving.sharded.ShardedCoordinator` whose shard
  objects are wire-RPC **proxies**: the barrier close, partial
  verification, hierarchical merge, ``fold_merge_finalize`` device
  step, cross-shard dedup, root WAL and per-shard confirmations all
  run over real sockets. The dial leg retries under PR 9's
  ``dial_policy`` (decorrelated jitter), so a recovering shard process
  is ridden out instead of failing the round.

Correctness is inherited, not re-implemented: the shard admission
plane, the verification cross-checks, the exactly-once dedup/WAL
contract and the hierarchical fold are the SAME code the in-process
tier runs — the runner only changes where each stage executes. Bit
parity vs the single frontend therefore holds at every topology
(pinned by ``tests/test_runner.py`` and the bench's ``--processes``
lane), and :func:`~byzpy_tpu.serving.sharded.audit_sharded_exactly_once`
audits the same WAL layout (``dir/shard<i>/…`` + ``dir/root/…``).

Failure drill: :meth:`Runner.kill_shard` SIGKILLs a shard process
(in-memory queues and ledgers GONE, only its WAL survives) and
:meth:`Runner.recover_shard` respawns it on the same durability
directory — the recovered process replays pending accepts, the root
dedup table drops anything already folded (``root_duplicate``), and
the cross-WAL audit must come back clean (the PR 12 failover drill,
promoted to real processes).

Trace stitching: with telemetry on, the root's round span context
rides the ``shard_close`` request frames (``wire.encode`` stamps dict
frames), each shard's ``serving.shard_close`` span adopts it, and the
``PartialFold.trace_ctx`` links ride back — ONE trace id spans the
shard, merge and root processes, and ``trace_export`` control frames
pull each process's events so the exports stitch into a single causal
tree (``observability.critical_path`` attributes the merged export
like any recorded trace).

Threat model: the runner authenticates the FABRIC (shared-key HMAC),
not individual processes. A compromised merge node can forge its whole
subtree's combined frame; the root's per-segment cross-checks bound
the blast radius to that subtree (ownership violations and digest
mismatches discard the frame, never a sibling's), and a deployment
with per-shard trust boundaries should give each process its own wire
key and verify sender↔index at the socket layer (docs/serving.md
§scale-out).
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import random
import socket
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from threading import Lock
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis import sanitize
from ..engine.actor import wire
from ..engine.actor.transports.tcp import dial_policy
from ..observability import metrics as obs_metrics
from ..observability import runtime as obs_runtime
from ..observability import tracing as obs_tracing
from ..resilience.durable import DurabilityConfig
from ..resilience.retry import RetryPolicy
from ..utils.platform import compile_cache_dir
from .frontend import LOSSLESS_REPLY, TenantConfig
from .sharded import (
    MergeTopology,
    PartialFold,
    ShardFrontend,
    ShardedCoordinator,
    combine_partials,
    shard_for,
)

#: Control-plane frame kinds the runner adds on top of the serving wire.
SHARD_CLOSE = "shard_close"
MERGE_CLOSE = "merge_close"
RUNNER_SHUTDOWN = "runner_shutdown"

_ACK = {"kind": "ack", "accepted": True}


@dataclass
class RunnerSpec:
    """Everything a child process needs to build its tier (cloudpickled
    to a spec file the ``--role`` entrypoints load).

    ``fanout=None`` is the flat depth-2 tier (root merges every shard
    directly); a fanout builds the depth-N merge tree —
    ``MergeTopology(n_shards, fanout)`` — with one merge-node process
    per internal group. ``durability_dir`` activates the PR 9 WAL on
    every shard (``dir/shard<i>``) and the root's merge-evidence WAL
    (``dir/root``), the exact layout ``audit_sharded_exactly_once``
    reads. ``shard_timeout_s`` is the leaf barrier budget; each merge
    level above adds ``level_slack_s`` to its parent's wait."""

    tenants: List[TenantConfig]
    n_shards: int
    fanout: Optional[int] = None
    host: str = "127.0.0.1"
    durability: Optional[DurabilityConfig] = None
    shard_timeout_s: float = 30.0
    level_slack_s: float = 15.0
    quorum: Optional[int] = None
    extras_policy: str = "trust"
    telemetry: bool = False
    #: speculative-close repair horizon (rounds) passed through to the
    #: root's :class:`ShardedCoordinator` — 0 keeps the classic
    #: degraded close (stragglers requeue at the barrier)
    repair_horizon_rounds: int = 0

    @property
    def topology(self) -> MergeTopology:
        """The merge-tree shape this spec deploys."""
        return MergeTopology(self.n_shards, self.fanout)

    def shard_durability(self, index: int) -> Optional[DurabilityConfig]:
        """The per-shard WAL config (``dir/shard<i>`` — the audit
        layout), or ``None`` when durability is off."""
        if self.durability is None:
            return None
        return dataclasses.replace(
            self.durability,
            directory=os.path.join(
                self.durability.directory, f"shard{index}"
            ),
        )


# ---------------------------------------------------------------------------
# blocking wire helpers (root + parent side: no event loop, real sockets)
# ---------------------------------------------------------------------------


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed mid-frame")
        buf += chunk
    return bytes(buf)


def recv_frame(sock: socket.socket) -> Any:
    """Read + decode one length-prefixed wire frame from a blocking
    socket (HMAC verified by ``wire.decode`` when signing is on)."""
    (length,) = wire._HEADER.unpack(_recv_exact(sock, wire._HEADER.size))
    if length > wire.MAX_FRAME:
        raise ValueError(f"frame too large: {length}")
    return wire.decode(_recv_exact(sock, length))


def send_frame(sock: socket.socket, obj: Any, *, lossless: bool = True) -> None:
    """Encode + write one frame. Runner control frames default to
    LOSSLESS — confirmation aggregates and partial rows are bit
    load-bearing, so ``BYZPY_TPU_WIRE_PRECISION`` must not apply."""
    sock.sendall(wire.encode(obj, precision="off" if lossless else None))


def rpc(sock: socket.socket, obj: Any, *, lossless: bool = True) -> Any:
    """One request/response round-trip on a blocking socket."""
    send_frame(sock, obj, lossless=lossless)
    return recv_frame(sock)


def dial_blocking(
    host: str,
    port: int,
    *,
    policy: Optional[RetryPolicy] = None,
    rng: Optional[random.Random] = None,
) -> socket.socket:
    """Blocking dial under PR 9's ``dial_policy`` (decorrelated-jitter
    backoff, attempt + deadline budgets) — a shard process mid-restart
    is ridden out instead of failing the proxy op."""
    policy = policy if policy is not None else dial_policy()
    rng = rng if rng is not None else random.Random()
    deadline = time.monotonic() + policy.deadline_s
    prev: Optional[float] = None
    last: Optional[BaseException] = None
    for attempt in range(policy.max_attempts):
        try:
            sock = socket.create_connection((host, port), timeout=10.0)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            return sock
        except OSError as exc:
            last = exc
        prev = policy.next_backoff_s(prev, rng)
        if attempt + 1 >= policy.max_attempts or (
            time.monotonic() + prev >= deadline
        ):
            break
        time.sleep(prev)
    raise ConnectionError(
        f"dial {host}:{port} failed after {policy.max_attempts} attempts"
    ) from last


# ---------------------------------------------------------------------------
# shard process (--role shard)
# ---------------------------------------------------------------------------


def _shard_hook(shard: ShardFrontend, stop: "asyncio.Event"):
    """The runner control plane, mounted on the shard ingress through
    ``ServingFrontend.request_hook`` (first look at every dict frame;
    returning ``None`` falls through to submit/stats)."""

    def hook(request: dict) -> Optional[dict]:
        kind = request.get("kind")
        if kind == SHARD_CLOSE:
            p = shard.close_partial(str(request.get("tenant")))
            return {
                "kind": "partial",
                "partial": None if p is None else p.to_wire(),
                LOSSLESS_REPLY: True,
            }
        if kind == "confirm":
            shard.confirm(
                str(request["tenant"]),
                int(request["round"]),
                [int(j) for j in request["folded"]],
                [int(j) for j in request["dups"]],
                str(request["digest"]),
                request["aggregate"],
                request.get("pre"),
            )
            return dict(_ACK)
        if kind == "requeue":
            shard.requeue(str(request["tenant"]), int(request["round"]))
            return dict(_ACK)
        if kind == "discard":
            shard.discard_inflight(
                str(request["tenant"]), int(request["round"])
            )
            return dict(_ACK)
        if kind == "account_failed":
            shard.account_failed(
                str(request["tenant"]), int(request["round"])
            )
            return dict(_ACK)
        if kind == "sync_round":
            shard.sync_round(str(request["tenant"]), int(request["round"]))
            return dict(_ACK)
        if kind == "shard_stats":
            return {"kind": "stats", "stats": shard.stats()}
        if kind == "trace_export":
            return {
                "kind": "trace",
                "events": obs_tracing.tracer().events(),
            }
        if kind == RUNNER_SHUTDOWN:
            stop.set()
            return dict(_ACK)
        if kind == "close_round":
            # rounds are coordinator-driven in runner mode: the inner
            # frontend's own closer would fork the round state
            return {
                "kind": "ack",
                "accepted": False,
                "reason": "coordinator_driven",
            }
        return None

    return hook


async def _shard_main(spec: RunnerSpec, index: int) -> None:
    shard = ShardFrontend(
        index, spec.tenants, durability=spec.shard_durability(index)
    )
    stop = asyncio.Event()
    shard.frontend.request_hook = _shard_hook(shard, stop)
    # the control hook returns None for "submit" with no side effects,
    # so the batched ingress may admit drained submit runs in one pass
    # without a per-frame hook call (declared, never inferred)
    shard.frontend.request_hook_passthrough = frozenset({"submit"})
    _host, port = await shard.frontend.serve(spec.host, 0)
    print(f"PORT {port}", flush=True)
    await stop.wait()
    # the shutdown ack is queued on the requesting connection; yield one
    # loop turn so it flushes before the server (and its conns) close
    await asyncio.sleep(0.05)
    await shard.frontend.close()


# ---------------------------------------------------------------------------
# merge-node process (--role merge)
# ---------------------------------------------------------------------------


class _MergeNode:
    """One internal merge-tree node: fans the close to its children,
    verifies every child frame, combines the survivors, ships one
    frame up. Stateless across rounds — all durable state lives at the
    leaves (WALs) and the root (dedup authority + merge evidence), so
    a merge-node crash is a plain partition the parent's timeout
    absorbs."""

    def __init__(
        self,
        spec: RunnerSpec,
        children: Sequence[Tuple[str, str, int, List[int]]],
    ) -> None:
        self.spec = spec
        #: (kind, host, port, covered leaves) per child — "shard"
        #: leaves answer shard_close, "merge" subtrees answer
        #: merge_close; the cover list feeds partition accounting when
        #: a whole child misses the barrier
        self.children = list(children)
        from .sharded import ShardRouter

        #: memoized home-shard lookup (the per-row ownership check
        #: runs every round over every child row)
        self._router = ShardRouter(spec.n_shards)
        self._streams: Dict[int, tuple] = {}
        #: per-child barrier budget, scaled by the child's OWN subtree
        #: depth: a merge child legitimately waits (leaf budget +
        #: slack·sublevels) before it can even reply, so its parent
        #: must wait one slack more — a flat leaf gets the bare budget
        self._child_timeouts = [
            spec.shard_timeout_s
            + spec.level_slack_s * (self._sublevels(cover) + 1)
            if kind == "merge"
            else spec.shard_timeout_s
            for kind, _h, _p, cover in self.children
        ]

    def _sublevels(self, cover: Sequence[int]) -> int:
        """Internal combine levels inside a merge child covering
        ``len(cover)`` leaves (0 when it combines leaves directly)."""
        if self.spec.fanout is None or len(cover) <= self.spec.fanout:
            return 0
        return len(MergeTopology(len(cover), self.spec.fanout).levels)

    async def _child_stream(self, i: int) -> tuple:
        st = self._streams.get(i)
        if st is None:
            from ..resilience.retry import connect_with_retry

            _kind, host, port, _cover = self.children[i]
            reader, writer = await connect_with_retry(
                host, port, policy=dial_policy(), component="merge_node"
            )
            st = self._streams[i] = (reader, writer, asyncio.Lock())
        return st

    async def _child_close(
        self, i: int, tenant: str, frame_bytes: bytes
    ) -> dict:
        timeout = self._child_timeouts[i]
        reader, writer, lock = await self._child_stream(i)
        async with lock:
            writer.write(frame_bytes)
            await writer.drain()
            header = await asyncio.wait_for(
                reader.readexactly(wire._HEADER.size), timeout
            )
            (length,) = wire._HEADER.unpack(header)
            body = await asyncio.wait_for(
                reader.readexactly(length), timeout
            )
            return wire.decode(body)

    def _verify_child(
        self, i: int, reply: dict
    ) -> Tuple[Optional[PartialFold], List[int], List[dict]]:
        """Decode + verify child ``i``'s close reply. Returns
        ``(partial or None, missing leaves, forged events)`` — digest
        recompute, per-row home-shard ownership AND the
        claimed-cover ⊆ child's-registered-cover check run HERE, so a
        poisoned child is excluded before it can brand its siblings'
        combined frame forged (or crash the combine by claiming a
        sibling's shard index) further up the tree."""
        from ..forensics.evidence import evidence_digest

        missing = [int(s) for s in reply.get("missing", ())]
        forged = [dict(ev) for ev in reply.get("forged", ())]
        raw = reply.get("partial")
        if raw is None:
            return None, missing, forged
        try:
            p = PartialFold.from_wire(raw)
        except (ValueError, KeyError, TypeError):
            return None, missing, forged
        registered = set(self.children[i][3])
        if not set(p.covered) <= registered:
            # a frame claiming shards outside this child's subtree: a
            # compromised child may forge a sibling's index with
            # legitimately-hashing client ids — without this check the
            # overlap would surface as a combine_partials ValueError
            # and take the WHOLE level down as missing
            forged.append(
                {
                    "shards": sorted(registered),
                    "claimed_digest": p.digest,
                    "measured_digest": "",
                    "m": p.m,
                }
            )
            return None, missing, forged
        measured = evidence_digest(p.rows)
        ownership_ok = all(
            self._router.shard_for(p.clients[j]) == owner
            for owner, lo, hi in p.segment_spans()
            for j in range(lo, hi)
        )
        if measured != p.digest or not ownership_ok:
            forged.append(
                {
                    "shards": list(p.covered),
                    "claimed_digest": p.digest,
                    "measured_digest": measured if ownership_ok else "",
                    "m": p.m,
                }
            )
            return None, missing, forged
        return p, missing, forged

    async def close(self, tenant: str, round_id: int) -> dict:
        """One level close: barrier the children, verify, combine."""
        with obs_tracing.span(
            "serving.merge_close", track="merge",
            tenant=tenant, round=round_id, children=len(self.children),
        ):
            frames = []
            for kind, _h, _p, _c in self.children:
                op = SHARD_CLOSE if kind == "shard" else MERGE_CLOSE
                frames.append(
                    wire.encode(
                        {"kind": op, "tenant": tenant, "round": round_id},
                        precision="off",
                    )
                )
            loop = asyncio.get_running_loop()

            async def _close_and_verify(i: int) -> tuple:
                # STREAMING fan-in: each child's frame is decoded and
                # verified on the executor the moment it lands, while
                # the siblings' closes are still in flight — by the
                # time the slowest child answers, every other child's
                # verify is already done and only the combine remains
                try:
                    reply = await self._child_close(i, tenant, frames[i])
                except Exception:  # noqa: BLE001 — timeout/reset/late
                    # child: a partition at this level; drop the stream
                    # (it may be mid-frame) and redial next round
                    st = self._streams.pop(i, None)
                    if st is not None:
                        st[1].close()
                    return None, self._leaves_of(i), []
                return await loop.run_in_executor(
                    None,
                    obs_tracing.carry_context(self._verify_child),
                    i, reply,
                )

            results = await asyncio.gather(
                *(_close_and_verify(i) for i in range(len(self.children)))
            )
            partials: List[PartialFold] = []
            missing: List[int] = []
            forged: List[dict] = []
            for p, child_missing, child_forged in results:
                missing.extend(child_missing)
                forged.extend(child_forged)
                if p is not None:
                    partials.append(p)
            combined = None
            if len(partials) == 1:
                combined = partials[0]
            elif partials:
                agg = self.spec.tenants[0].aggregator
                for cfg in self.spec.tenants:
                    if cfg.name == tenant:
                        agg = cfg.aggregator
                        break
                try:
                    combined = combine_partials(agg, partials)
                except ValueError:
                    # belt and braces: _verify_child's cover check
                    # should make this unreachable, but a combine
                    # failure must degrade to "this level missed the
                    # barrier" (missing leaves requeue at the root),
                    # never kill the merge node's connection handler
                    combined = None
                    missing.extend(
                        s for p in partials for s in p.covered
                    )
            return {
                "kind": "partial",
                "partial": None if combined is None else combined.to_wire(),
                "missing": sorted(set(missing)),
                "forged": forged,
                LOSSLESS_REPLY: True,
            }

    def _leaves_of(self, i: int) -> List[int]:
        """Leaf shard indices under child ``i`` (for partition
        accounting when the whole child misses the barrier)."""
        return list(self.children[i][3])

    async def child_moved(self, shard: int, port: int) -> bool:
        """A recovered shard process came back on a new port: update
        the child entry that covers it (or forward down the subtree),
        dropping the stale stream so the next close redials."""
        for j, (kind, host, _old, cover) in enumerate(self.children):
            if shard not in cover:
                continue
            if kind == "shard":
                self.children[j] = (kind, host, int(port), cover)
                st = self._streams.pop(j, None)
                if st is not None:
                    st[1].close()
                return True
            reader, writer, lock = await self._child_stream(j)
            async with lock:
                writer.write(
                    wire.encode(
                        {
                            "kind": "child_moved",
                            "shard": int(shard),
                            "port": int(port),
                        },
                        precision="off",
                    )
                )
                await writer.drain()
                header = await asyncio.wait_for(
                    reader.readexactly(wire._HEADER.size), 30.0
                )
                (length,) = wire._HEADER.unpack(header)
                await asyncio.wait_for(reader.readexactly(length), 30.0)
            return True
        return False


async def _merge_main(
    spec: RunnerSpec, children: Sequence[Tuple[str, str, int, List[int]]]
) -> None:
    node = _MergeNode(spec, children)
    stop = asyncio.Event()

    async def handle(reader, writer):
        try:
            while True:
                try:
                    header = await reader.readexactly(wire._HEADER.size)
                except (asyncio.IncompleteReadError, ConnectionError):
                    break
                (length,) = wire._HEADER.unpack(header)
                body = await reader.readexactly(length)
                request = wire.decode(body)
                kind = (
                    request.get("kind")
                    if isinstance(request, dict)
                    else None
                )
                if kind == MERGE_CLOSE:
                    resp = await node.close(
                        str(request["tenant"]), int(request["round"])
                    )
                elif kind == "child_moved":
                    moved = await node.child_moved(
                        int(request["shard"]), int(request["port"])
                    )
                    resp = {"kind": "ack", "accepted": bool(moved)}
                elif kind == "trace_export":
                    resp = {
                        "kind": "trace",
                        "events": obs_tracing.tracer().events(),
                    }
                elif kind == RUNNER_SHUTDOWN:
                    resp = dict(_ACK)
                else:
                    resp = {
                        "kind": "ack",
                        "accepted": False,
                        "reason": "bad_frame",
                    }
                lossless = bool(resp.pop(LOSSLESS_REPLY, False))
                writer.write(
                    wire.encode(
                        resp, precision="off" if lossless else None
                    )
                )
                await writer.drain()
                if kind == RUNNER_SHUTDOWN:
                    stop.set()
                    break
        finally:
            writer.close()

    server = await asyncio.start_server(handle, spec.host, 0)
    port = server.sockets[0].getsockname()[1]
    print(f"PORT {port}", flush=True)
    await stop.wait()
    server.close()
    await server.wait_closed()
    for _r, w, _l in node._streams.values():
        w.close()


# ---------------------------------------------------------------------------
# root coordinator process (--role root)
# ---------------------------------------------------------------------------


class _ShardProxy:
    """The root's wire-RPC stand-in for one shard process: answers the
    ``ShardFrontend`` coordinator surface (confirm/requeue/discard/
    account_failed/sync_round/stats) by sending control frames to the
    shard's ingress. Ops are best-effort pushes whose loss maps to
    existing recovery semantics (a lost confirm is the ship-folded-
    but-unconfirmed window the root dedup table already resolves), so
    a dead socket marks the op failed and the next op redials under
    ``dial_policy``."""

    def __init__(self, index: int, host: str, port: int) -> None:
        self.index = int(index)
        self.host = host
        self.port = int(port)
        self.alive = True
        self._sock: Optional[socket.socket] = None
        self.failed_ops = 0
        # pipelined closes run round N's confirm fan-out on the finish
        # thread while the control thread syncs/polls the same shard —
        # the socket carries one op at a time or frames interleave
        self._op_lock = Lock()

    def _ensure(self) -> socket.socket:
        if self._sock is None:
            self._sock = dial_blocking(self.host, self.port)
        return self._sock

    def reset(self) -> None:
        """Drop the cached connection (next op redials)."""
        if self._sock is not None:
            try:
                self._sock.close()
            finally:
                self._sock = None

    def move(self, port: int) -> None:
        """Point the proxy at a recovered shard process."""
        self.port = int(port)
        self.reset()
        self.alive = True

    def op(self, frame: dict, *, timeout: float = 30.0) -> Optional[dict]:
        """One control round-trip; one reconnect retry; ``None`` when
        the shard is unreachable (the op is lost, accounted)."""
        if not self.alive:
            return None
        with self._op_lock:
            for _attempt in (0, 1):
                try:
                    sock = self._ensure()
                    sock.settimeout(timeout)
                    return rpc(sock, frame)
                except (OSError, ValueError, ConnectionError):
                    self.reset()
            self.failed_ops += 1
            return None

    # -- the coordinator-facing surface -----------------------------------

    def confirm(
        self, tenant, round_id, folded, dups, digest, aggregate, pre=None
    ) -> None:
        self.op(
            {
                "kind": "confirm",
                "tenant": tenant,
                "round": int(round_id),
                "folded": [int(j) for j in folded],
                "dups": [int(j) for j in dups],
                "digest": digest,
                "aggregate": np.asarray(aggregate, np.float32),
                "pre": pre,
            }
        )

    def requeue(self, tenant, round_id) -> None:
        self.op({"kind": "requeue", "tenant": tenant, "round": int(round_id)})

    def discard_inflight(self, tenant, round_id) -> None:
        self.op({"kind": "discard", "tenant": tenant, "round": int(round_id)})

    def account_failed(self, tenant, round_id) -> None:
        self.op(
            {
                "kind": "account_failed",
                "tenant": tenant,
                "round": int(round_id),
            }
        )

    def sync_round(self, tenant, round_id) -> None:
        self.op(
            {"kind": "sync_round", "tenant": tenant, "round": int(round_id)}
        )

    def stats(self) -> Optional[dict]:
        reply = self.op({"kind": "shard_stats"})
        return None if reply is None else reply.get("stats")

    def shutdown(self) -> None:
        """Lifecycle belongs to the parent Runner — the coordinator's
        close() must not tear down shard processes."""


class _RootServer:
    """The root coordinator process: a proxied ``ShardedCoordinator``
    plus a control-plane TCP server for the operator (close_round /
    stats / shard_down / shard_up / trace_export / shutdown). Round
    closes fan the barrier to the TOP tier (leaf shards on the flat
    topology, merge nodes on a deep one) with one thread per child —
    the close request frames are encoded on the coordinator thread so
    the round span's trace context stamps them (contextvars are
    thread-local)."""

    def __init__(
        self,
        spec: RunnerSpec,
        shard_addrs: Sequence[Tuple[str, int]],
        top_children: Sequence[Tuple[str, str, int, List[int]]],
    ) -> None:
        self.spec = spec
        self.proxies = [
            _ShardProxy(i, host, port)
            for i, (host, port) in enumerate(shard_addrs)
        ]
        self.co = ShardedCoordinator(
            spec.tenants,
            spec.n_shards,
            shard_timeout_s=spec.shard_timeout_s,
            quorum=spec.quorum,
            durability=spec.durability,
            extras_policy=spec.extras_policy,
            shards=self.proxies,
            repair_horizon_rounds=spec.repair_horizon_rounds,
        )
        #: (kind, host, port, covered leaves) per top-tier child
        self.top = list(top_children)
        self._top_socks: Dict[int, socket.socket] = {}
        depth_levels = len(spec.topology.levels)
        self._close_timeout = spec.shard_timeout_s + (
            spec.level_slack_s * max(1, depth_levels)
        )
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, len(self.top)),
            thread_name_prefix="root-barrier",
        )
        self._lock = Lock()
        self._stop = False
        # cross-round pipelining: depth-1 in-flight window per tenant.
        # A pipelined close barriers round N on the control thread,
        # then hands verify+merge+device-step to this 1-worker pool and
        # returns — the shard processes ingest round N+1 while the
        # finish runs. The NEXT close settles the pending finish before
        # barriering, so finishes serialize (WAL round records stay
        # monotonic) and backpressure still reaches the door.
        self._finish_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="root-finish"
        )
        self._pending: Dict[str, dict] = {}
        reg = obs_metrics.registry()
        self._m_overlap = {
            cfg.name: reg.gauge(
                "byzpy_round_overlap_ratio",
                help=(
                    "fraction of the deferred round finish that ran "
                    "hidden behind next-round ingest"
                ),
                labels={"tenant": cfg.name},
            )
            for cfg in spec.tenants
        }

    # -- barrier close -----------------------------------------------------

    def _top_sock(self, i: int) -> socket.socket:
        sock = self._top_socks.get(i)
        if sock is None:
            _kind, host, port, _cover = self.top[i]
            sock = self._top_socks[i] = dial_blocking(host, port)
        return sock

    def _reset_top(self, i: int) -> None:
        sock = self._top_socks.pop(i, None)
        if sock is not None:
            sock.close()

    def _barrier(
        self, tenant: str, round_id: int
    ) -> Tuple[List[PartialFold], List[int], Dict[int, tuple]]:
        """Fan one round's close to the top tier and collect the
        replies: returns ``(partials, missing_set, prechecked)``.
        STREAMING verify: each reader thread decodes its child's frame
        and runs the root's stateless cross-check suite
        (``check_partial`` — digest recompute, ownership, caps) the
        moment the frame lands, overlapped with the siblings still in
        flight — ``prechecked`` maps ``id(partial)`` to the result so
        the merge runs only the dedup. No shard-state side effects —
        requeue/merge policy belongs to the callers (the classic door
        requeues stragglers immediately; a speculative close leaves
        them in flight for the repair horizon)."""
        missing: List[int] = [
            p.index for p in self.proxies if not p.alive
        ]
        live_top = [
            i
            for i, (_k, _h, _p, cover) in enumerate(self.top)
            if any(self.proxies[s].alive for s in cover)
        ]
        # encode on THIS thread: the frames carry the round span's
        # trace context into every child process
        frames = {}
        for i in live_top:
            kind = self.top[i][0]
            op = SHARD_CLOSE if kind == "shard" else MERGE_CLOSE
            frames[i] = wire.encode(
                {"kind": op, "tenant": tenant, "round": round_id},
                precision="off",
            )

        def barrier(i: int) -> tuple:
            sock = self._top_sock(i)
            sock.settimeout(self._close_timeout)
            sock.sendall(frames[i])
            reply = recv_frame(sock)
            raw = reply.get("partial")
            if raw is None:
                return reply, None, None
            try:
                p = PartialFold.from_wire(raw)
            except (ValueError, KeyError, TypeError):
                return reply, None, "bad_partial"
            chk = self.co.check_partial(tenant, p, inflight=True)
            if chk[0]:
                # close-path paydown: stage the dedup verdict + merge
                # input on this reader thread while siblings are still
                # in flight — the root close just promotes
                self.co.stage_partial(tenant, p, chk)
            return reply, p, chk

        futures = {
            self._pool.submit(
                obs_tracing.carry_context(barrier), i
            ): i
            for i in live_top
        }
        partials: List[PartialFold] = []
        prechecked: Dict[int, tuple] = {}
        for fut, i in futures.items():
            cover = self.top[i][3]
            try:
                reply, p, chk = fut.result(
                    timeout=self._close_timeout + 5.0
                )
            except Exception:  # noqa: BLE001 — timeout / dead child:
                # the whole subtree missed the barrier; its socket
                # may be mid-frame, reset it
                self._reset_top(i)
                missing.extend(
                    s for s in cover if self.proxies[s].alive
                )
                continue
            missing.extend(int(s) for s in reply.get("missing", ()))
            for ev in reply.get("forged", ()):
                # one forged FRAME = one count + one evidence
                # event, however many leaves it covered (the
                # flat-root accounting; discard fans per leaf)
                shards = [
                    int(s)
                    for s in ev.get("shards", (ev.get("shard"),))
                    if s is not None
                ]
                if not shards:
                    continue
                self.co.note_forged(
                    tenant,
                    shards,
                    claimed_digest=str(
                        ev.get("claimed_digest", "")
                    ),
                    measured_digest=str(
                        ev.get("measured_digest", "")
                    ),
                    m=int(ev.get("m", 0)),
                )
            if chk == "bad_partial":
                missing.extend(
                    s for s in cover if self.proxies[s].alive
                )
            elif p is not None:
                partials.append(p)
                prechecked[id(p)] = chk
        return partials, sorted(set(missing)), prechecked

    def _requeue_missing(
        self, tenant: str, missing: Sequence[int], round_id: int
    ) -> None:
        """Return missing-but-ALIVE leaves' drained cohorts to their
        held lists. A leaf may have drained for a close whose reply
        never reached us (straggler past the barrier, merge-node
        timeout): requeue it explicitly or its inflight rows strand
        forever — the shard's event loop serializes the frames, so the
        requeue lands AFTER any still-running close finishes
        (idempotent when the leaf drained nothing)."""
        for s in missing:
            if self.proxies[s].alive:
                self.proxies[s].requeue(tenant, round_id)

    def close_round(self, tenant: str) -> Optional[tuple]:
        """One root-driven barrier round over real sockets: fan the
        close to the top tier, decode + account replies, quorum-gate,
        then run the coordinator's verify + hierarchical merge +
        finalize + confirm protocol through the shard proxies. Returns
        ``(closed_round_id, merged_rows, aggregate)`` or ``None``.
        With the repair horizon armed, stragglers are NOT requeued at
        the barrier — the coordinator retains the speculative close's
        repair context and the horizon expiry recycles them."""
        self._settle(tenant)
        rt = self.co._roots[tenant]
        with obs_tracing.span(
            "serving.sharded_round", track="root",
            tenant=tenant, round=rt.round_id,
        ):
            partials, missing_set, prechecked = self._barrier(
                tenant, rt.round_id
            )
            speculative = self.co.repair_horizon > 0
            if not speculative:
                self._requeue_missing(tenant, missing_set, rt.round_id)
            responders = self.spec.n_shards - len(missing_set)
            if responders < self.co.quorum:
                if prechecked:
                    # no merge consumes the arrival checks: unwind
                    self.co._dec_inflight(len(prechecked))
                for p in partials:
                    for s in p.covered:
                        self.proxies[s].requeue(tenant, p.round_id)
                if speculative:
                    self._requeue_missing(
                        tenant, missing_set, rt.round_id
                    )
                rt.quorum_failures += 1
                return None
            if not partials:
                if speculative:
                    self._requeue_missing(
                        tenant, missing_set, rt.round_id
                    )
                return None
            res = self.co.merge_partials(
                tenant, partials, missing=missing_set,
                prechecked=prechecked,
            )
            if res is None and speculative:
                # no close happened — nothing to repair into; recycle
                # the stragglers exactly as the classic path
                self._requeue_missing(tenant, missing_set, rt.round_id)
            return res

    # -- pipelined close (cross-round overlap) -----------------------------

    def close_round_pipelined(self, tenant: str) -> dict:
        """The ALWAYS-ON round door: settle the previous round's
        deferred finish (depth-1 window — this is where backpressure
        bites), barrier round N on this thread, and if quorum fired
        hand verify+merge+device-step to the finish pool and return
        immediately with the next round's admission plane OPEN (shard
        staleness clocks advance optimistically; the ROOT clock stays
        at N until the finish lands, so partial round-id checks still
        pass). Returns ``{"pending": N | None, "prev": <settled round
        N-1 summary | None>, "round": <admitting round>}``. A window
        with no admissible close settles and returns with ``pending:
        None`` — semantics identical to the barrier door."""
        prev = self._settle(tenant)
        rt = self.co._roots[tenant]
        out: dict = {"pending": None, "prev": prev, "round": rt.round_id}
        sp = obs_tracing.begin_span(
            "serving.sharded_round", track="root",
            tenant=tenant, round=rt.round_id, pipelined=True,
        )
        kicked = False
        try:
            with obs_tracing.context_scope(getattr(sp, "context", None)):
                partials, missing_set, prechecked = self._barrier(
                    tenant, rt.round_id
                )
                speculative = self.co.repair_horizon > 0
                if not speculative:
                    self._requeue_missing(
                        tenant, missing_set, rt.round_id
                    )
                responders = self.spec.n_shards - len(missing_set)
                if responders < self.co.quorum:
                    if prechecked:
                        # no merge consumes the arrival checks: unwind
                        self.co._dec_inflight(len(prechecked))
                    for p in partials:
                        for s in p.covered:
                            self.proxies[s].requeue(tenant, p.round_id)
                    if speculative:
                        self._requeue_missing(
                            tenant, missing_set, rt.round_id
                        )
                    rt.quorum_failures += 1
                    return out
                if not partials:
                    if speculative:
                        self._requeue_missing(
                            tenant, missing_set, rt.round_id
                        )
                    return out
            # quorum fired: open round N+1's admission/staleness plane
            # NOW — the shard processes ingest the next round while the
            # finish below runs on the 1-worker pool; the sync fans in
            # PARALLEL (the kick is the serialized part of the pipeline,
            # every sequential round-trip here is unhidden latency)
            closing = rt.round_id
            sync_futs = [
                self._pool.submit(p.sync_round, tenant, closing + 1)
                for p in self.proxies
                if p.alive
            ]
            for f in sync_futs:
                f.result(timeout=self._close_timeout + 5.0)
            entry: dict = {
                "round": closing,
                "kicked": time.monotonic(),
                "done_s": None,
            }
            entry["future"] = self._finish_pool.submit(
                self._deferred_finish,
                tenant, closing, partials, missing_set, prechecked,
                sp, entry,
            )
            self._pending[tenant] = entry
            kicked = True  # span ownership moved to the finish thread
            out["pending"] = closing
            out["round"] = closing + 1
            return out
        finally:
            if not kicked:
                obs_tracing.end_span(sp)

    def _deferred_finish(
        self,
        tenant: str,
        closing: int,
        partials: List[PartialFold],
        missing: List[int],
        prechecked: Dict[int, tuple],
        sp,
        entry: dict,
    ) -> Optional[tuple]:
        """The overlapped half of a pipelined close: verify +
        hierarchical merge + finalize + confirm through the proxies,
        off the control thread. On a failed merge the round is CONSUMED
        anyway (the shard clocks already advanced optimistically, so
        the root clock must follow) — the drained rows requeue and fold
        next round one round staler, the only behavioral divergence
        from the barrier path and only in the failure case."""
        try:
            with obs_tracing.context_scope(getattr(sp, "context", None)):
                res = self.co.merge_partials(
                    tenant, partials, missing=missing,
                    prechecked=prechecked,
                )
            if res is None:
                rt = self.co._roots[tenant]
                rt.round_id = closing + 1
                for p in self.proxies:
                    if p.alive:
                        p.sync_round(tenant, closing + 1)
                if self.co.repair_horizon > 0:
                    self._requeue_missing(tenant, missing, closing)
            return res
        finally:
            entry["done_s"] = time.monotonic()
            obs_tracing.end_span(sp)

    def _settle(self, tenant: str) -> Optional[dict]:
        """Wait out the tenant's pending deferred finish (no-op when
        none): returns the settled round's summary (``closed``/
        ``digest``/``m``/``overlap_ratio``) and publishes the
        ``byzpy_round_overlap_ratio`` gauge — the fraction of the
        finish that ran before anyone had to wait for it, i.e. the
        wall-clock the pipeline actually hid."""
        entry = self._pending.pop(tenant, None)
        if entry is None:
            return None
        wait_start = time.monotonic()
        try:
            res = entry["future"].result(
                timeout=self._close_timeout + 30.0
            )
        except Exception:  # noqa: BLE001 — a crashed finish must not
            # wedge the control door; the round's accounting is
            # whatever the coordinator got to
            res = None
        prev: dict = {"closed": None, "round": int(entry["round"])}
        if res is not None:
            from ..forensics.evidence import evidence_digest

            closed, rows, vec = res
            prev["closed"] = int(closed)
            prev["digest"] = evidence_digest(np.asarray(vec))
            prev["m"] = int(rows.shape[0])
        done_s = entry.get("done_s") or wait_start
        span_s = max(0.0, done_s - entry["kicked"])
        hidden = max(0.0, min(done_s, wait_start) - entry["kicked"])
        ratio = 1.0 if span_s <= 0 else max(0.0, min(1.0, hidden / span_s))
        prev["overlap_ratio"] = round(ratio, 4)
        if obs_runtime.STATE.enabled and tenant in self._m_overlap:
            self._m_overlap[tenant].set(ratio)
        return prev

    # -- control plane -----------------------------------------------------

    def handle(self, request: dict) -> dict:
        kind = request.get("kind")
        if kind == "close_round":
            tenant = str(request.get("tenant"))
            if request.get("pipelined"):
                with self._lock:
                    out = self.close_round_pipelined(tenant)
                return {
                    "kind": "round",
                    "closed": None,
                    "pending": out["pending"],
                    "prev": out["prev"],
                    "round": out["round"],
                    LOSSLESS_REPLY: True,
                }
            with self._lock:
                res = self.close_round(tenant)
            resp: dict = {
                "kind": "round",
                "closed": None,
                "round": self.co.round_of(tenant),
                LOSSLESS_REPLY: True,
            }
            if res is not None:
                from ..forensics.evidence import evidence_digest

                closed, rows, vec = res
                resp["closed"] = closed
                resp["digest"] = evidence_digest(np.asarray(vec))
                resp["m"] = int(rows.shape[0])
                if request.get("return_rows"):
                    resp["rows"] = np.asarray(rows, np.float32)
                    resp["aggregate"] = np.asarray(vec, np.float32)
            return resp
        if kind == "flush_rounds":
            tenant = str(request.get("tenant"))
            with self._lock:
                prev = self._settle(tenant)
                current = self.co.round_of(tenant)
            return {
                "kind": "round",
                "prev": prev,
                "round": current,
                LOSSLESS_REPLY: True,
            }
        if kind == "repair_round":
            tenant = str(request.get("tenant"))
            with self._lock:
                self._settle(tenant)
                try:
                    partial = PartialFold.from_wire(
                        request.get("partial")
                    )
                except (ValueError, KeyError, TypeError):
                    return {
                        "kind": "ack",
                        "accepted": False,
                        "reason": "bad_partial",
                    }
                # arrival-verified once, reused by the repair — a late
                # frame costs ONE cross-check run end to end
                chk = self.co.check_partial(
                    tenant, partial, inflight=True
                )
                res = self.co.repair_round(
                    tenant, partial, prechecked=chk
                )
            resp = {
                "kind": "round",
                "closed": None,
                "round": self.co.round_of(tenant),
                LOSSLESS_REPLY: True,
            }
            if res is not None:
                from ..forensics.evidence import evidence_digest

                closed, rows, vec = res
                resp["closed"] = closed
                resp["digest"] = evidence_digest(np.asarray(vec))
                resp["m"] = int(rows.shape[0])
            return resp
        if kind == "stats":
            with self._lock:
                return {"kind": "stats", "stats": self.co.stats()}
        if kind == "shard_down":
            with self._lock:
                idx = int(request["index"])
                self.proxies[idx].alive = False
                self.proxies[idx].reset()
                self.co._m_live.set(
                    sum(1 for p in self.proxies if p.alive)
                )
            return dict(_ACK)
        if kind == "shard_up":
            with self._lock:
                idx = int(request["index"])
                port = int(request["port"])
                self.proxies[idx].move(port)
                # the barrier path must learn the new address too: a
                # flat top entry is rewritten in place, a merge subtree
                # gets a child_moved frame to route down
                for i, (k, h, _old, cover) in enumerate(self.top):
                    if idx not in cover:
                        continue
                    if k == "shard":
                        self.top[i] = (k, h, port, cover)
                        self._reset_top(i)
                    else:
                        try:
                            sock = self._top_sock(i)
                            sock.settimeout(30.0)
                            rpc(
                                sock,
                                {
                                    "kind": "child_moved",
                                    "shard": idx,
                                    "port": port,
                                },
                            )
                        except (OSError, ValueError, ConnectionError):
                            self._reset_top(i)
                    break
                for name, rt in self.co._roots.items():
                    self.proxies[idx].sync_round(name, rt.round_id)
                self.co._m_live.set(
                    sum(1 for p in self.proxies if p.alive)
                )
            return dict(_ACK)
        if kind == "shard_events":
            with self._lock:
                return {
                    "kind": "events",
                    "events": list(self.co.shard_events),
                }
        if kind == "trace_export":
            return {
                "kind": "trace",
                "events": obs_tracing.tracer().events(),
            }
        if kind == RUNNER_SHUTDOWN:
            self._stop = True
            return dict(_ACK)
        return {"kind": "ack", "accepted": False, "reason": "bad_frame"}

    def shutdown(self) -> None:
        # settle any pending deferred finishes BEFORE tearing sockets
        # down — a mid-flight confirm fan-out must land (WAL round
        # records are the audit trail)
        for tenant in list(self._pending):
            entry = self._pending.pop(tenant, None)
            if entry is None:
                continue
            try:
                entry["future"].result(timeout=self._close_timeout + 30.0)
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        with self._lock:
            for rt in self.co._roots.values():
                if rt.durability is not None:
                    rt.durability.close()
            for sock in self._top_socks.values():
                sock.close()
            for p in self.proxies:
                p.reset()
        self._pool.shutdown(wait=False)
        self._finish_pool.shutdown(wait=False)


def _root_main(
    spec: RunnerSpec,
    shard_addrs: Sequence[Tuple[str, int]],
    top_children: Sequence[Tuple[str, str, int, List[int]]],
) -> None:
    root = _RootServer(spec, shard_addrs, top_children)
    server = socket.create_server((spec.host, 0))
    port = server.getsockname()[1]
    print(f"PORT {port}", flush=True)
    server.settimeout(0.5)
    conns: List = []

    def serve_conn(sock: socket.socket) -> None:
        # idle-wait in 1 s slices so every control thread notices
        # _stop and drains (the executor's exit joins them)
        sock.settimeout(1.0)
        try:
            while not root._stop:
                try:
                    request = recv_frame(sock)
                except socket.timeout:
                    continue
                except (ConnectionError, ValueError, OSError):
                    break
                sock.settimeout(None)
                try:
                    resp = root.handle(
                        request if isinstance(request, dict) else {}
                    )
                except Exception as exc:  # noqa: BLE001 — a bad operator
                    # frame must not kill the control plane
                    resp = {
                        "kind": "ack",
                        "accepted": False,
                        "reason": f"error: {type(exc).__name__}: {exc}",
                    }
                lossless = bool(resp.pop(LOSSLESS_REPLY, False))
                try:
                    sock.sendall(
                        wire.encode(
                            resp, precision="off" if lossless else None
                        )
                    )
                except OSError:
                    break
                sock.settimeout(1.0)
        finally:
            sock.close()

    with ThreadPoolExecutor(
        max_workers=8, thread_name_prefix="root-ctl"
    ) as ctl:
        while not root._stop:
            # accept times out every 0.5 s, so a 30 s tick gap means the
            # control plane itself wedged (not an idle fabric)
            sanitize.loop_tick("runner.root_accept", threshold_s=30.0)
            try:
                sock, _addr = server.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conns.append(ctl.submit(serve_conn, sock))
    server.close()
    root.shutdown()


# ---------------------------------------------------------------------------
# parent-side runner (spawns + manages the process fleet)
# ---------------------------------------------------------------------------


class _Child:
    """One spawned tier process (shard / merge / root)."""

    def __init__(
        self, role: str, index: int, proc: subprocess.Popen, port: int
    ) -> None:
        self.role = role
        self.index = index
        self.proc = proc
        self.port = port

    def sigkill(self) -> None:
        self.proc.kill()
        self.proc.wait()

    def stop(self, timeout: float = 15.0) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:  # pragma: no cover
                self.proc.kill()
                self.proc.wait()


def _read_port(proc: subprocess.Popen, what: str) -> int:
    import select

    assert proc.stdout is not None
    deadline = time.monotonic() + 180
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        # select before readline: a wedged child that stays alive
        # without printing must trip the deadline, not block the
        # spawner forever (the PORT line is one flushed write, so a
        # ready fd yields a complete line)
        ready, _, _ = select.select(
            [proc.stdout], [], [], min(1.0, remaining)
        )
        if not ready:
            if proc.poll() is not None:
                raise RuntimeError(f"{what} died before printing PORT")
            continue
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(f"{what} died before printing PORT")
        if line.startswith("PORT "):
            return int(line.split()[1])
    raise RuntimeError(f"{what} never printed PORT within 180s")


class Runner:
    """Spawn and drive one process-per-shard deployment: N shard
    processes, the merge-node processes the topology asks for, and the
    root coordinator process — all on this host, all over real TCP
    sockets (the zero-shared-state shape a multi-host deployment
    copies with different addresses).

    Use as a context manager; :meth:`close` performs a DRAINED
    shutdown (control-frame stop to every child, SIGTERM fallback) and
    raises if any process survives — no orphans is part of the
    contract the CI smoke asserts."""

    def __init__(self, spec: RunnerSpec) -> None:
        self.spec = spec
        self.shards: List[_Child] = []
        self.merges: List[_Child] = []
        self.root: Optional[_Child] = None
        self._workdir: Optional[tempfile.TemporaryDirectory] = None
        self._spec_path: Optional[str] = None
        self._ctl: Optional[socket.socket] = None

    def __enter__(self) -> "Runner":
        self.start()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    # -- lifecycle ---------------------------------------------------------

    def _spawn(self, role: str, index: int, extra: List[str]) -> _Child:
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        # children share the checkout's compile cache (never a directory
        # under the runner's temporary workdir: the path is part of the key)
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
        if self.spec.telemetry:
            env["BYZPY_TPU_TELEMETRY"] = "1"
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "byzpy_tpu.serving.runner",
                "--role", role, "--spec", str(self._spec_path),
                "--index", str(index), *extra,
            ],
            stdout=subprocess.PIPE,
            text=True,
            env=env,
        )
        port = _read_port(proc, f"{role}{index}")
        return _Child(role, index, proc, port)

    def start(self) -> None:
        """Spawn the fleet bottom-up (shards → merge levels → root) and
        connect the operator control socket. A spawn failure partway
        tears the already-started children back down (no orphans on
        the failure path either)."""
        if self.root is not None:
            return
        try:
            self._start()
        except BaseException:
            self.close()
            raise

    def _start(self) -> None:
        import cloudpickle

        self._workdir = tempfile.TemporaryDirectory(prefix="byzpy-runner-")
        self._spec_path = os.path.join(self._workdir.name, "spec.pkl")
        with open(self._spec_path, "wb") as f:
            f.write(cloudpickle.dumps(self.spec))
        spec = self.spec
        self.shards = [
            self._spawn("shard", i, []) for i in range(spec.n_shards)
        ]
        # tier: (kind, host, port, covered leaves) per live node,
        # leaf-most first; each merge level groups the previous tier
        tier: List[Tuple[str, str, int, List[int]]] = [
            ("shard", spec.host, c.port, [i])
            for i, c in enumerate(self.shards)
        ]
        merge_index = 0
        for level in spec.topology.levels:
            nxt: List[Tuple[str, str, int, List[int]]] = []
            for group in level:
                children = [
                    node for node in tier if node[3][0] in group
                ]
                child = self._spawn(
                    "merge",
                    merge_index,
                    [
                        "--children",
                        json.dumps(
                            [
                                [k, h, p, cover]
                                for k, h, p, cover in children
                            ]
                        ),
                    ],
                )
                self.merges.append(child)
                merge_index += 1
                nxt.append(
                    (
                        "merge",
                        spec.host,
                        child.port,
                        sorted(s for node in children for s in node[3]),
                    )
                )
            tier = nxt
        self.root = self._spawn(
            "root",
            0,
            [
                "--shards",
                json.dumps([[spec.host, c.port] for c in self.shards]),
                "--children",
                json.dumps([[k, h, p, cover] for k, h, p, cover in tier]),
            ],
        )
        self._ctl = dial_blocking(spec.host, self.root.port)

    @property
    def shard_ports(self) -> List[int]:
        """Ingress port per shard (clients submit here directly)."""
        return [c.port for c in self.shards]

    def _control(self, frame: dict, *, timeout: float = 600.0) -> dict:
        assert self._ctl is not None, "start() first"
        self._ctl.settimeout(timeout)
        return rpc(self._ctl, frame)

    # -- operator surface --------------------------------------------------

    def close_round(
        self, tenant: str, *, return_rows: bool = False
    ) -> dict:
        """Drive one barrier round at the root (over its control
        socket); the reply carries the closed round id + aggregate
        digest (+ merged rows/aggregate bits when asked — the parity
        checks in tests and the bench read them)."""
        return self._control(
            {
                "kind": "close_round",
                "tenant": tenant,
                "return_rows": bool(return_rows),
            }
        )

    def close_round_pipelined(self, tenant: str) -> dict:
        """Kick one PIPELINED round at the root: the reply returns as
        soon as the barrier + quorum gate land — round N's verify/
        merge/device step keeps running at the root while the shards
        admit round N+1. The reply carries ``pending`` (the round now
        finishing, or ``None`` when the window had nothing), ``prev``
        (the PREVIOUS pipelined round's settled summary — closed id,
        digest, m, overlap_ratio) and ``round`` (the round now
        admitting). Call :meth:`flush_rounds` to settle the last
        in-flight round."""
        return self._control(
            {"kind": "close_round", "tenant": tenant, "pipelined": True}
        )

    def flush_rounds(self, tenant: str) -> dict:
        """Settle the tenant's in-flight pipelined round (no-op when
        none): the reply's ``prev`` is the settled summary."""
        return self._control({"kind": "flush_rounds", "tenant": tenant})

    def stats(self) -> dict:
        """Root + per-shard accounting (the proxies poll each shard)."""
        return self._control({"kind": "stats"})["stats"]

    def shard_events(self) -> List[dict]:
        """The root's bounded shard-event tail (forgeries, quorum
        closes)."""
        return self._control({"kind": "shard_events"})["events"]

    def trace_exports(self) -> Dict[str, List[dict]]:
        """Pull every process's tracer events (``{"root": [...],
        "shard0": [...], "merge0": [...]}``) for cross-process
        stitching — each process prefixes its span ids with its pid,
        so the merged event list is collision-free by construction."""
        out: Dict[str, List[dict]] = {}
        out["root"] = self._control({"kind": "trace_export"})["events"]
        for child in [*self.shards, *self.merges]:
            if child.proc.poll() is not None:
                continue
            sock = dial_blocking(self.spec.host, child.port)
            try:
                sock.settimeout(30.0)
                reply = rpc(sock, {"kind": "trace_export"})
                out[f"{child.role}{child.index}"] = reply.get(
                    "events", []
                )
            finally:
                sock.close()
        return out

    # -- failure drill -----------------------------------------------------

    def kill_shard(self, index: int) -> None:
        """SIGKILL the shard process (memory gone, WAL survives) and
        tell the root — its clients get ``rejected_shard_down``-shaped
        connection failures until recovery."""
        self.shards[index].sigkill()
        self._control({"kind": "shard_down", "index": index})

    def recover_shard(self, index: int) -> None:
        """Respawn the killed shard on the SAME durability directory
        (WAL-rebuild: pending accepts re-enter its queue, dedup +
        credit totals replay) and point the root's proxy at the new
        port."""
        child = self._spawn("shard", index, [])
        self.shards[index] = child
        self._control(
            {"kind": "shard_up", "index": index, "port": child.port}
        )

    def close(self) -> None:
        """Drained shutdown: stop children via control frames, SIGTERM
        stragglers, assert nothing survives."""
        children: List[_Child] = []
        if self.root is not None:
            children.append(self.root)
        children.extend(self.merges)
        children.extend(self.shards)
        if self._ctl is not None:
            try:
                self._control({"kind": RUNNER_SHUTDOWN}, timeout=15.0)
            except Exception:  # noqa: BLE001 — root already gone
                pass
            self._ctl.close()
            self._ctl = None
        for child in [*self.merges, *self.shards]:
            if child.proc.poll() is not None:
                continue
            try:
                sock = dial_blocking(
                    self.spec.host, child.port,
                    policy=RetryPolicy(
                        max_attempts=2, base_s=0.05, cap_s=0.2,
                        deadline_s=2.0,
                    ),
                )
                try:
                    sock.settimeout(10.0)
                    rpc(sock, {"kind": RUNNER_SHUTDOWN})
                finally:
                    sock.close()
            except Exception:  # noqa: BLE001 — already exiting
                pass
        for child in children:
            child.stop()
        leaked = [
            f"{c.role}{c.index}" for c in children if c.proc.poll() is None
        ]
        self.root = None
        self.merges = []
        self.shards = []
        if self._workdir is not None:
            self._workdir.cleanup()
            self._workdir = None
        if leaked:  # pragma: no cover — the no-orphans contract
            raise RuntimeError(f"runner leaked processes: {leaked}")


# ---------------------------------------------------------------------------
# client (routing + pipelined submission)
# ---------------------------------------------------------------------------


class RunnerClient:
    """Blocking client for a runner deployment: routes each submission
    to its home shard's ingress (the same sticky blake2s hash every
    tier participant derives) and supports WINDOWED PIPELINING —
    ``submit_many`` keeps up to ``window`` frames in flight per shard
    connection so the wire stays full without unbounded ack buffering
    (the per-frame request/response shape stays intact; only the
    interleaving changes)."""

    def __init__(
        self, host: str, shard_ports: Sequence[int], *, window: int = 256
    ) -> None:
        self.host = host
        self.ports = list(shard_ports)
        self.window = int(window)
        self._socks: Dict[int, socket.socket] = {}

    @property
    def n_shards(self) -> int:
        """Shard count (the routing modulus)."""
        return len(self.ports)

    def _sock(self, shard: int) -> socket.socket:
        sock = self._socks.get(shard)
        if sock is None:
            sock = self._socks[shard] = dial_blocking(
                self.host, self.ports[shard]
            )
        return sock

    def encode_submit(
        self,
        tenant: str,
        client: str,
        round_id: int,
        gradient: np.ndarray,
        *,
        seq: Optional[int] = None,
    ) -> Tuple[int, bytes]:
        """Pre-encode one submit frame; returns ``(home_shard,
        frame_bytes)`` so benches can build a round's traffic outside
        the timed region."""
        return (
            shard_for(client, self.n_shards),
            wire.encode(
                {
                    "kind": "submit",
                    "tenant": tenant,
                    "client": client,
                    "round": int(round_id),
                    "gradient": gradient,
                    "seq": seq,
                }
            ),
        )

    def submit(
        self,
        tenant: str,
        client: str,
        round_id: int,
        gradient: np.ndarray,
        *,
        seq: Optional[int] = None,
    ) -> dict:
        """One routed submission round-trip."""
        shard, frame = self.encode_submit(
            tenant, client, round_id, gradient, seq=seq
        )
        sock = self._sock(shard)
        sock.settimeout(60.0)
        sock.sendall(frame)
        return recv_frame(sock)

    def pipeline(self, shard: int, frames: Sequence[bytes]) -> List[dict]:
        """Send ``frames`` to one shard with windowed pipelining and
        return the acks in order."""
        sock = self._sock(shard)
        sock.settimeout(120.0)
        acks: List[dict] = []
        w = self.window
        for lo in range(0, len(frames), w):
            chunk = frames[lo: lo + w]
            sock.sendall(b"".join(chunk))
            for _ in chunk:
                acks.append(recv_frame(sock))
        return acks

    def submit_many(
        self, frames_by_shard: Dict[int, List[bytes]]
    ) -> Tuple[int, int]:
        """Drive every shard's frame list concurrently (one thread per
        shard — the threads only move bytes, the shard processes do
        the decode + admission work). Returns ``(accepted,
        rejected)``."""
        accepted = 0
        rejected = 0

        def drive(shard: int) -> Tuple[int, int]:
            acks = self.pipeline(shard, frames_by_shard[shard])
            ok = sum(1 for a in acks if a.get("accepted"))
            return ok, len(acks) - ok

        live = [s for s, frames in frames_by_shard.items() if frames]
        if not live:
            return 0, 0
        with ThreadPoolExecutor(max_workers=len(live)) as pool:
            for ok, bad in pool.map(drive, live):
                accepted += ok
                rejected += bad
        return accepted, rejected

    def close(self) -> None:
        """Close every shard connection."""
        for sock in self._socks.values():
            sock.close()
        self._socks.clear()


# ---------------------------------------------------------------------------
# CLI (child roles + the CI smoke)
# ---------------------------------------------------------------------------


def _load_spec(path: str) -> RunnerSpec:
    import cloudpickle

    with open(path, "rb") as f:
        return cloudpickle.loads(f.read())


def _smoke() -> None:
    """CI leg: 2 shard processes + root over real sockets — parity vs
    the single frontend asserted bit-for-bit, bounded wall-clock,
    drained shutdown leaves no orphan processes."""
    from ..aggregators import CoordinateWiseTrimmedMean

    t0 = time.monotonic()
    dim, n_clients, rounds = 64, 12, 3
    spec = RunnerSpec(
        tenants=[
            TenantConfig(
                name="m0",
                aggregator=CoordinateWiseTrimmedMean(f=1),
                dim=dim,
                cohort_cap=64,
                queue_capacity=128,
            )
        ],
        n_shards=2,
        telemetry=True,
    )
    rng = np.random.default_rng(0)
    ref_agg = CoordinateWiseTrimmedMean(f=1)
    barrier_digests: List[str] = []
    with Runner(spec) as runner:
        client = RunnerClient("127.0.0.1", runner.shard_ports)
        try:
            for r in range(rounds):
                frames: Dict[int, List[bytes]] = {0: [], 1: []}
                for i in range(n_clients):
                    shard, frame = client.encode_submit(
                        "m0", f"c{i:03d}", r,
                        rng.normal(size=dim).astype(np.float32), seq=r,
                    )
                    frames[shard].append(frame)
                accepted, rejected = client.submit_many(frames)
                assert accepted == n_clients and rejected == 0, (
                    accepted, rejected,
                )
                reply = runner.close_round("m0", return_rows=True)
                assert reply["closed"] == r, reply
                barrier_digests.append(reply["digest"])
                rows = np.asarray(reply["rows"])
                ref = np.asarray(
                    ref_agg.aggregate(
                        [rows[i] for i in range(rows.shape[0])]
                    )
                )
                assert np.array_equal(
                    np.asarray(reply["aggregate"]), ref
                ), f"runner parity diverged at round {r}"
            # streaming leg: the frames were verified on the reader
            # threads the moment they landed (check_partial at arrival)
            # and every arrival-verified frame was consumed by a close
            st = runner.stats()["root"]["m0"]
            assert st["partial_checks"] >= rounds, st
            assert st["partials_inflight"] == 0, st
            # close-path paydown: every frame's dedup verdict staged on
            # its reader thread, every close settled off the staged
            # accumulator, zero redundant per-partial transforms
            assert st["dedup_staged"] >= 2 * rounds, st
            assert st["dedup_promoted"] >= 2 * rounds, st
            assert st["dedup_restaged"] == 0, st
            assert st["staged_closes"] == rounds, st
            assert st["partial_transforms"] == 0, st
            stream_checks = st["partial_checks"]
            exports = runner.trace_exports()
        finally:
            client.close()
    # -- pipelined leg: IDENTICAL traffic through the always-on door —
    # round N+1's frames must be admitted while round N's finish is
    # still in flight at the root, and every settled digest must match
    # the barrier door's bit-for-bit
    rng = np.random.default_rng(0)
    overlap_admitted = 0
    pipelined_digests: List[str] = []
    with Runner(spec) as runner:
        client = RunnerClient("127.0.0.1", runner.shard_ports)
        try:
            def _build(r: int) -> Dict[int, List[bytes]]:
                frames: Dict[int, List[bytes]] = {0: [], 1: []}
                for i in range(n_clients):
                    shard, frame = client.encode_submit(
                        "m0", f"c{i:03d}", r,
                        rng.normal(size=dim).astype(np.float32), seq=r,
                    )
                    frames[shard].append(frame)
                return frames

            accepted, rejected = client.submit_many(_build(0))
            assert accepted == n_clients and rejected == 0
            for r in range(rounds):
                reply = runner.close_round_pipelined("m0")
                assert reply["pending"] == r, reply
                if r > 0:
                    prev = reply["prev"]
                    assert prev and prev["closed"] == r - 1, reply
                    pipelined_digests.append(prev["digest"])
                if r + 1 < rounds:
                    # admission for round N+1 while round N's verify/
                    # merge/device step runs deferred at the root — the
                    # acks land BEFORE anything settles round N
                    accepted, rejected = client.submit_many(
                        _build(r + 1)
                    )
                    assert accepted == n_clients and rejected == 0, (
                        accepted, rejected,
                    )
                    overlap_admitted += accepted
            tail = runner.flush_rounds("m0")
            prev = tail["prev"]
            assert prev and prev["closed"] == rounds - 1, tail
            pipelined_digests.append(prev["digest"])
            st = runner.stats()["root"]["m0"]
            assert st["partial_checks"] >= rounds, st
            assert st["partials_inflight"] == 0, st
            # pipelined door: staging survives the cross-round overlap
            # (epoch revalidation, never a verdict flip on this traffic)
            assert st["dedup_restaged"] == 0, st
            assert st["staged_closes"] == rounds, st
            assert st["partial_transforms"] == 0, st
        finally:
            client.close()
    assert overlap_admitted > 0, "no frames admitted during overlap"
    assert pipelined_digests == barrier_digests, (
        "pipelined close diverged from the barrier door",
        pipelined_digests, barrier_digests,
    )
    # one causal tree across processes: a root round span's trace id
    # must appear in at least one shard process's export
    root_traces = {
        ev["args"]["trace"]
        for ev in exports["root"]
        if ev.get("name") == "serving.sharded_round"
        and "trace" in ev.get("args", {})
    }
    shard_traces = {
        ev["args"]["trace"]
        for name, events in exports.items()
        if name.startswith("shard")
        for ev in events
        if "trace" in ev.get("args", {})
    }
    assert root_traces & shard_traces, (
        "cross-process trace stitching broke: no shared trace id"
    )
    wall = time.monotonic() - t0
    assert wall < 300, f"runner smoke took {wall:.1f}s (budget 300s)"
    print(
        json.dumps(
            {
                "lane": "runner_smoke",
                "rounds": rounds,
                "parity": "bit-identical",
                "pipelined_parity": "bit-identical",
                "streaming_checks": stream_checks,
                "overlap_admitted": overlap_admitted,
                "stitched_traces": len(root_traces & shard_traces),
                "wall_s": round(wall, 2),
            }
        )
    )
    print("runner smoke OK")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--role", choices=["shard", "merge", "root"])
    ap.add_argument("--spec", type=str, default=None)
    ap.add_argument("--index", type=int, default=0)
    ap.add_argument("--children", type=str, default="[]",
                    help="JSON [[kind, host, port, [leaves]], ...]")
    ap.add_argument("--shards", type=str, default="[]",
                    help="JSON [[host, port], ...] (root role)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI leg: 2-shard runner, parity + no orphans")
    args = ap.parse_args()
    if args.smoke:
        _smoke()
        return
    if not args.role:
        raise SystemExit("need --role or --smoke")
    if not args.spec:
        raise SystemExit("--role requires --spec")
    spec = _load_spec(args.spec)
    if spec.telemetry and not obs_runtime.STATE.enabled:
        from .. import observability

        observability.enable()
    if args.role == "shard":
        asyncio.run(_shard_main(spec, args.index))
        return
    children = [
        (str(k), str(h), int(p), [int(s) for s in cover])
        for k, h, p, cover in json.loads(args.children)
    ]
    if args.role == "merge":
        asyncio.run(_merge_main(spec, children))
        return
    shard_addrs = [
        (str(h), int(p)) for h, p in json.loads(args.shards)
    ]
    _root_main(spec, shard_addrs, children)


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()


__all__ = [
    "MERGE_CLOSE",
    "RUNNER_SHUTDOWN",
    "SHARD_CLOSE",
    "Runner",
    "RunnerClient",
    "RunnerSpec",
    "dial_blocking",
    "recv_frame",
    "rpc",
    "send_frame",
]
