"""Kill-and-recover drill: SIGKILL a real TCP frontend, prove recovery.

Two fault lanes, both driven against PRODUCTION code paths (a real
``ServingFrontend`` speaking real wire frames over real sockets — no
simulated fault events):

* :func:`run_kill_recover` — a frontend subprocess with durability on is
  SIGKILLed mid-round (submissions acked ``accepted`` but not yet
  folded), restarted on the same directory, and the drill then replays
  the ambiguous submissions (the client never saw whether its acks
  survived) plus fresh traffic. Asserted invariants:

  1. **No accepted-then-lost submissions** — every ``(client, seq)``
     acked ``accepted`` before the kill appears in the write-ahead
     log's fold records exactly once after final drain.
  2. **Exactly-once folding** — replayed frames answer
     ``accepted=True, reason="duplicate"`` and never re-fold.
  3. **Monotonic rounds** — round ids across the kill are strictly
     increasing and contiguous; no id is reissued.
  4. **Digest continuity** — the aggregate digests the restarted
     process's WAL carries for pre-kill rounds match what the client
     observed live.

* :func:`run_wire_drop` — in-process: the same submission schedule runs
  once directly and once through a seeded fault proxy that forwards
  submit frames upstream and then kills the connection BEFORE the ack
  comes back (the worst ambiguity: effect applied, ack lost). Clients
  retry under a :class:`~byzpy_tpu.resilience.retry.RetryPolicy`;
  per-round aggregates must match the no-fault run bit for bit.

CLI: ``python -m byzpy_tpu.resilience.drill --smoke`` is the CI leg
(kill-and-recover + wire-drop, must finish well under 60 s);
``--serve --dir D`` is the subprocess server mode the drill spawns.
``benchmarks/chaos_bench.py --lanes recovery`` fans the same functions
across ≥ 20 seeds as the standing regression wall.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..utils.platform import compile_cache_dir

#: Above the wire codec's WIRE_QUANT_MIN_SIZE floor, so the EF-residual
#: leg's s4 downlink actually quantizes (a smaller dim would ride the
#: lossless small-array path and the residual invariants would be
#: vacuously zero).
DIM = 2048
TENANT = "drill"


def _tenant_config(breaker: bool = False):
    from ..aggregators import CoordinateWiseMedian
    from ..resilience.breaker import BreakerPolicy
    from ..serving import TenantConfig

    return TenantConfig(
        name=TENANT,
        aggregator=CoordinateWiseMedian(),
        dim=DIM,
        window_s=0.05,
        cohort_cap=64,
        queue_capacity=256,
        breaker=BreakerPolicy(threshold=4, cooldown_s=0.5) if breaker else None,
    )


def _durability(directory: str):
    from ..resilience.durable import DurabilityConfig

    # snapshot often, keep every generation, and keep the full WAL
    # history (prune=False) so the verification pass can audit
    # exactly-once folding over the run's whole life
    return DurabilityConfig(
        directory=directory, snapshot_every=2, max_to_keep=8, prune=False
    )


# ---------------------------------------------------------------------------
# server mode (the subprocess the drill kills)
# ---------------------------------------------------------------------------


async def _serve(directory: str) -> None:
    from .. import observability
    from ..engine.actor import wire
    from ..serving import ServingFrontend
    from ..serving.frontend import LOSSLESS_REPLY

    observability.enable()
    fe = ServingFrontend(
        [_tenant_config()], durability=_durability(directory)
    )

    def hook(request):
        # downlink door for the EF-residual leg: the client pulls the
        # tenant's compressed (s4 + error-feedback) model broadcast —
        # the encode mutates the residual the snapshot must cover. The
        # reply re-ships the DECODED downlink lossless, which is
        # exactly the array a real client holds after decoding.
        if request.get("kind") == "model":
            try:
                frame = fe.broadcast_frame(TENANT, precision="s4")
            except RuntimeError:
                return {"kind": "model", "aggregate": None}
            payload = wire.decode(frame[4:])
            return {
                "kind": "model",
                "aggregate": payload["aggregate"],
                "round": payload["round"],
                LOSSLESS_REPLY: True,
            }
        return None

    fe.request_hook = hook
    host, port = await fe.serve("127.0.0.1", 0)
    rec = fe.recovered.get(TENANT)
    print(f"PORT {port}", flush=True)
    print(
        f"RECOVERED {json.dumps(None if rec is None else rec.round_id)}",
        flush=True,
    )
    await asyncio.Event().wait()  # until killed


# ---------------------------------------------------------------------------
# kill-and-recover lane
# ---------------------------------------------------------------------------


class _Server:
    """One frontend subprocess on a durability directory."""

    def __init__(self, directory: str) -> None:
        self.directory = directory
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
        env["BYZPY_TPU_TELEMETRY"] = "1"
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "byzpy_tpu.resilience.drill",
             "--serve", "--dir", directory],
            stdout=subprocess.PIPE,
            text=True,
            env=env,
        )
        self.port = self._read_port()

    def _read_port(self) -> int:
        assert self.proc.stdout is not None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = self.proc.stdout.readline()
            if not line:
                raise RuntimeError("drill server died before printing PORT")
            if line.startswith("PORT "):
                return int(line.split()[1])
        raise RuntimeError("drill server never printed PORT")

    def sigkill(self) -> None:
        self.proc.kill()  # SIGKILL on POSIX: no atexit, no flush, no mercy
        self.proc.wait()

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:  # pragma: no cover
                self.proc.kill()
                self.proc.wait()


def _grad(rng: np.random.Generator) -> np.ndarray:
    return rng.normal(size=DIM).astype(np.float32)


async def _drive_kill_recover(seed: int, directory: str) -> dict:
    from ..resilience.retry import RetryPolicy
    from ..serving import ServingClient

    rng = np.random.default_rng(seed)
    policy = RetryPolicy(max_attempts=8, base_s=0.05, cap_s=0.5, deadline_s=30.0)
    acked: List[Tuple[str, int]] = []  # every (client, seq) acked accepted
    live_digests: Dict[int, str] = {}  # round -> digest the client SAW

    server = _Server(directory)
    t0 = time.monotonic()
    try:
        async with ServingClient(retry=policy) as c:
            await c.connect("127.0.0.1", server.port)
            # phase 1: a clean folded round the recovery must preserve
            for i in range(6):
                ack = await c.submit(TENANT, f"c{i}", 0, _grad(rng))
                assert ack["accepted"], ack
                acked.append((f"c{i}", ack_seq(c)))
            r = await c.close_round(TENANT)
            assert r["closed"] == 0, r
            live_digests[0] = r["digest"]
            # EF-residual leg, phase A: pull the compressed (s4 + error
            # feedback) model broadcast so the tenant carries a residual,
            # then close a second round — the snapshot_every=2 cadence
            # snapshots AT that close, capturing the residual
            model = await c._call({"kind": "model", "tenant": TENANT})  # noqa: SLF001
            assert model["aggregate"] is not None
            for i in range(6):
                ack = await c.submit(TENANT, f"c{i}", 1, _grad(rng))
                assert ack["accepted"], ack
                acked.append((f"c{i}", ack_seq(c)))
            r = await c.close_round(TENANT)
            assert r["closed"] == 1, r
            live_digests[1] = r["digest"]
            # the residual the snapshot should have captured (recorded
            # BEFORE the next pull mutates it past the snapshot)
            ef_at_snapshot = (await c.stats(TENANT))["stats"][
                "ef_residual_norm"
            ]
            model = await c._call({"kind": "model", "tenant": TENANT})  # noqa: SLF001
            # phase 2: accepted-but-unfolded submissions, then the kill.
            # The client records these as AMBIGUOUS (it will replay them).
            ambiguous: List[Tuple[str, int, np.ndarray]] = []
            for i in range(5):
                g = _grad(rng)
                ack = await c.submit(TENANT, f"c{i}", 2, g)
                assert ack["accepted"], ack
                seq = ack_seq(c)
                acked.append((f"c{i}", seq))
                ambiguous.append((f"c{i}", seq, g))
        server.sigkill()

        # restart on the same directory: constructor-recovery
        server2 = _Server(directory)
        try:
            async with ServingClient(retry=policy) as c:
                await c.connect("127.0.0.1", server2.port)
                # replay the ambiguous frames under their ORIGINAL seqs —
                # the dedup layer must absorb them (accepted, duplicate)
                dup = 0
                for client, seq, g in ambiguous:
                    ack = await c.submit(TENANT, client, 2, g, seq=seq)
                    assert ack["accepted"], ack
                    dup += ack["reason"] == "duplicate"
                # fresh post-recovery traffic across several rounds (at
                # least snapshot_every of them, so the restarted process
                # also exercises the periodic snapshot), then drain
                closed_rounds = []

                async def close_all():
                    while True:
                        r = await c.close_round(TENANT)
                        if r["closed"] is None:
                            return
                        closed_rounds.append(r["closed"])
                        live_digests[r["closed"]] = r["digest"]

                # EF-residual leg, phase B: the recovered residual is
                # either the snapshot's BIT-EXACT capture (same norm to
                # the last float) or None (WAL-tail-only recovery /
                # snapshot save lost to the kill) — the documented
                # safe-to-reset branch
                ef_recovered = (await c.stats(TENANT))["stats"][
                    "ef_residual_norm"
                ]
                if ef_recovered is not None:
                    ef_branch = "snapshot_bitexact"
                    ef_ok = ef_recovered == ef_at_snapshot
                else:
                    ef_branch = "reset_safe"
                    ef_ok = True  # non-divergence asserted below
                ef_norms_post = []
                for phase in range(3):
                    for i in range(4):
                        ack = await c.submit(TENANT, f"c{i}", 2, _grad(rng))
                        assert ack["accepted"], ack
                        acked.append((f"c{i}", ack_seq(c)))
                    await close_all()
                    # keep the downlink EF stream alive across recovery:
                    # every pull must stay a bounded, non-divergent
                    # residual (no silent divergence after recover)
                    model = await c._call(  # noqa: SLF001
                        {"kind": "model", "tenant": TENANT}
                    )
                    agg = np.asarray(model["aggregate"], np.float32)
                    stats_now = (await c.stats(TENANT))["stats"]
                    ef_norms_post.append(stats_now["ef_residual_norm"])
                    # residual bound: one round's s4 quantization error,
                    # generously slacked (absmax/14 per coordinate x 4)
                    bound = 4 * float(np.abs(agg).max()) / 14 * np.sqrt(DIM)
                    ef_ok = ef_ok and (
                        ef_norms_post[-1] is not None
                        and ef_norms_post[-1] <= bound
                    )
                stats = (await c.stats(TENANT))["stats"]
                metrics_text = await _scrape(server2.port)
        finally:
            server2.stop()
    finally:
        server.stop()

    wall_s = time.monotonic() - t0
    inv = _verify_wal(directory, acked, live_digests)
    inv.update(
        {
            "seed": seed,
            "wall_s": round(wall_s, 3),
            "duplicates_absorbed": dup,
            "outstanding_after_drain": stats["outstanding"],
            "recovered_from": stats["recovered_from"],
            "ef_branch": ef_branch,
            "ef_residual_ok": bool(ef_ok),
            "ef_norms_post_recovery": ef_norms_post,
            "recovery_metric_exported": "byzpy_recoveries_total" in metrics_text,
            "retry_metric_exported": "byzpy_retry_total" in metrics_text,
            "checkpoint_metric_exported": (
                "byzpy_checkpoint_save_seconds" in metrics_text
            ),
        }
    )
    inv["violations"] += int(stats["outstanding"] != 0)
    inv["violations"] += int(stats["recovered_from"] is None)
    inv["violations"] += int(dup != len(ambiguous))
    inv["violations"] += int(not ef_ok)
    return inv


def ack_seq(client) -> int:
    """The seq the client just auto-assigned (its counter post-incremented)."""
    return client._seq - 1  # noqa: SLF001 — drill introspection


async def _scrape(port: int) -> str:
    """One raw Prometheus scrape off the wire ingress."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(b"GET /metrics HTTP/1.0\r\n\r\n")
        await writer.drain()
        data = await reader.read(-1)
        return data.decode(errors="replace")
    finally:
        writer.close()


def _verify_wal(
    directory: str,
    acked: List[Tuple[str, int]],
    live_digests: Dict[int, str],
) -> dict:
    """Read the tenant's whole WAL history and check the drill invariants."""
    from ..resilience.durable import ACCEPT, DROP, ROUND, RoundLog, TenantDurability

    tdir = os.path.join(directory, TENANT)
    segs = sorted(
        f for f in os.listdir(tdir) if f.startswith("wal-") and f.endswith(".log")
    )
    accepts: Dict[int, Tuple[str, Optional[int]]] = {}
    fold_counts: Dict[int, int] = {}
    rounds: List[Tuple[int, str]] = []
    dropped: set = set()
    for name in segs:
        records, _clean = RoundLog.read(os.path.join(tdir, name))
        for r in records:
            if r[0] == ACCEPT:
                accepts[r[1]] = (r[2], r[3])
            elif r[0] == ROUND:
                rounds.append((int(r[1]), r[3]))
                for w in r[2]:
                    fold_counts[w] = fold_counts.get(w, 0) + 1
            elif r[0] == DROP:
                dropped.update(r[2])
    by_key: Dict[Tuple[str, int], int] = {}
    for w, n in fold_counts.items():
        client, seq = accepts.get(w, ("?", None))
        if seq is not None:
            key = (client, int(seq))
            by_key[key] = by_key.get(key, 0) + n
    lost = [k for k in acked if by_key.get(k, 0) == 0]
    double = [k for k in acked if by_key.get(k, 0) > 1]
    round_ids = [r for r, _ in sorted(rounds)]
    monotonic = round_ids == sorted(set(round_ids)) and round_ids == list(
        range(round_ids[0], round_ids[0] + len(round_ids))
    ) if round_ids else True
    digest_breaks = [
        r for r, d in rounds if r in live_digests and live_digests[r] != d
    ]
    violations = len(lost) + len(double) + len(digest_breaks) + int(not monotonic)
    # TenantDurability's own reader must agree with the raw scan
    td = TenantDurability(_durability(directory), TENANT)
    rec = td.recovered
    td.close()
    violations += int(rec is None or rec.pending != [])
    return {
        "lane": "recovery_kill",
        "acked_accepted": len(acked),
        "folded_once": sum(1 for k in acked if by_key.get(k, 0) == 1),
        "lost": len(lost),
        "double_folded": len(double),
        "rounds": round_ids,
        "rounds_monotonic": bool(monotonic),
        "digest_breaks": len(digest_breaks),
        "violations": violations,
    }


def run_kill_recover(seed: int, directory: str) -> dict:
    """One seeded SIGKILL-mid-round / recover / drain cycle (blocking)."""
    return asyncio.run(_drive_kill_recover(seed, directory))


# ---------------------------------------------------------------------------
# wire-drop lane (in-process, deterministic)
# ---------------------------------------------------------------------------


class _AckDropProxy:
    """Seeded fault proxy: forwards each submit frame upstream, then for
    chosen frame indices kills the connection BEFORE relaying the ack —
    the worst-case ambiguity (effect applied, ack lost)."""

    def __init__(self, upstream_port: int, drop_frames: set) -> None:
        self.upstream_port = upstream_port
        self.drop = drop_frames
        self._count = 0
        self._server: Optional[asyncio.AbstractServer] = None
        self.port = 0

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle, "127.0.0.1", 0
        )
        self.port = self._server.sockets[0].getsockname()[1]

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()

    async def _handle(self, reader, writer) -> None:
        from ..engine.actor import wire

        up_r, up_w = await asyncio.open_connection(
            "127.0.0.1", self.upstream_port
        )
        try:
            while True:
                try:
                    header = await reader.readexactly(wire._HEADER.size)
                    (length,) = wire._HEADER.unpack(header)
                    body = await reader.readexactly(length)
                except (asyncio.IncompleteReadError, ConnectionError):
                    break
                idx = self._count
                self._count += 1
                up_w.write(header + body)
                await up_w.drain()
                try:
                    r_header = await up_r.readexactly(wire._HEADER.size)
                    (r_len,) = wire._HEADER.unpack(r_header)
                    r_body = await up_r.readexactly(r_len)
                except (asyncio.IncompleteReadError, ConnectionError):
                    break
                if idx in self.drop:
                    break  # ack swallowed: the client must retry
                writer.write(r_header + r_body)
                await writer.drain()
        finally:
            for w in (writer, up_w):
                w.close()


async def _drive_wire_drop(seed: int) -> dict:
    from ..resilience.retry import RetryPolicy
    from ..serving import ServingClient, ServingFrontend

    rng = np.random.default_rng(seed)
    n_subs, n_rounds = 18, 3
    schedule = [
        (f"w{int(i % 6)}", _grad(rng)) for i in range(n_subs)
    ]
    close_at = {5, 11, 17}  # close a round after these submission indices

    async def run(drop_frames: set) -> Tuple[List[str], dict]:
        fe = ServingFrontend([_tenant_config()])
        host, port = await fe.serve("127.0.0.1", 0)
        proxy = _AckDropProxy(port, drop_frames)
        await proxy.start()
        digests = []
        try:
            async with ServingClient(
                retry=RetryPolicy(
                    max_attempts=6, base_s=0.01, cap_s=0.05, deadline_s=10.0
                )
            ) as c:
                await c.connect("127.0.0.1", proxy.port)
                for i, (cid, g) in enumerate(schedule):
                    ack = await c.submit(TENANT, cid, fe.round_of(TENANT), g)
                    assert ack["accepted"], (i, ack)
                    if i in close_at:
                        closed = fe.close_round_nowait(TENANT)
                        assert closed is not None
                        from ..serving.frontend import _agg_digest

                        digests.append(_agg_digest(closed[2]))
                stats = fe.stats()[TENANT]
        finally:
            await proxy.stop()
            await fe.close()
        return digests, stats

    clean_digests, clean_stats = await run(set())
    # drop the ack of ~1 in 4 submit frames (seeded); retries make the
    # frame counter drift, so sample generously across the schedule
    drops = set(
        int(i) for i in rng.choice(n_subs, size=max(2, n_subs // 4), replace=False)
    )
    fault_digests, fault_stats = await run(drops)
    parity = clean_digests == fault_digests
    # the retry counters live in THIS process (the clients retried here)
    from ..observability import metrics as obs_metrics

    snap = obs_metrics.registry().snapshot()
    retry_total = sum(
        v["value"] for k, v in snap.items()
        if k.startswith("byzpy_retry_total")
    )
    return {
        "lane": "recovery_wire",
        "seed": seed,
        "acks_dropped": len(drops),
        "duplicates_absorbed": fault_stats["duplicates"],
        "rounds": len(fault_digests),
        "bit_parity": bool(parity),
        "retry_total": retry_total,
        "violations": int(not parity)
        + int(fault_stats["duplicates"] < 1)
        + int(clean_stats["duplicates"] != 0),
    }


def run_wire_drop(seed: int) -> dict:
    """One seeded ack-drop/retry cycle with bit-parity check (blocking)."""
    return asyncio.run(_drive_wire_drop(seed))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--serve", action="store_true", help="server mode")
    ap.add_argument("--dir", type=str, default=None, help="durability dir")
    ap.add_argument("--smoke", action="store_true",
                    help="CI leg: one kill-recover + one wire-drop, <60s")
    ap.add_argument("--seed", type=int, default=20260804)
    args = ap.parse_args()
    if args.serve:
        if not args.dir:
            raise SystemExit("--serve requires --dir")
        asyncio.run(_serve(args.dir))
        return
    import tempfile

    t0 = time.monotonic()
    with tempfile.TemporaryDirectory() as tmp:
        kill_row = run_kill_recover(args.seed, tmp)
    wire_row = run_wire_drop(args.seed)
    wall = time.monotonic() - t0
    print(json.dumps(kill_row))
    print(json.dumps(wire_row))
    print(json.dumps({"lane": "drill_meta", "wall_s": round(wall, 3)}))
    if args.smoke:
        assert kill_row["violations"] == 0, kill_row
        assert wire_row["violations"] == 0, wire_row
        assert kill_row["recovery_metric_exported"], kill_row
        assert kill_row["checkpoint_metric_exported"], kill_row
        assert wire_row["retry_total"] >= 1, wire_row
        assert wall < 60, f"drill smoke took {wall:.1f}s (budget 60s)"
        print("recovery drill smoke OK")


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()


__all__ = ["run_kill_recover", "run_wire_drop"]
