"""Multi-host failover coordinator (DESIGN.md §14, ROADMAP item 4).

Replaces the long-standing ``runtime/ft.py:coordinator()`` stub with the
real control loop, realized at container scale: *hosts* are logical
ingest workers over a ``jax.distributed``-style process group (the same
abstraction that one device per host stands in for: virtual CPU
devices, or the chips of one TPU host), and the sharded
backend maps one register shard per live host. The loop composes three
pieces that already existed separately:

* **durability** — ``engine.checkpoint_state()`` pushed through
  ``ckpt.AsyncCheckpointer`` every ``ckpt_every`` blocks, so manifest
  writes overlap ingest compute;
* **elastic restore** — on a lost host, ``engine.load(..., shards=S-1)``
  re-hosts the newest *complete* manifest on the surviving mesh
  (DESIGN.md §12; partially-written step directories are never visible
  to ``latest_step``);
* **resume** — ingestion restarts from the restored ``m_ingested``
  cursor, which is always a block boundary because checkpoints are taken
  between blocks.

Loss detection is heartbeat/lease based: every live host deposits a
heartbeat per block tick (unless the fault plan drops it); a host whose
last beat is ``lease_blocks`` ticks stale is evicted exactly like a
killed one. ``runtime.ft``'s retry and straggler machinery is wired into
the same loop — transient block failures retry ``max_retries`` times,
and per-block wall time feeds the warmup-aware ``StragglerWatchdog``.

Run ``python -m repro.runtime.coordinator --smoke`` for the end-to-end
kill-one-host demonstration CI uses (asserts recovered answers are
bit-identical to an uninterrupted build).
"""
from __future__ import annotations

import os
import sys

from repro.launch import jaxenv

if __name__ == "__main__":
    # --smoke needs a multi-device mesh: virtual devices on the CPU only,
    # before jax loads; an accelerator's visible devices are used as is.
    jaxenv.virtual_cpu_devices(8)

import math
import time
from dataclasses import dataclass

import numpy as np

from repro import engine
from repro.ckpt.checkpoint import AsyncCheckpointer, latest_step
from repro.runtime.faults import FaultInjector, HostLost
from repro.runtime.ft import FTConfig, StragglerWatchdog

__all__ = ["CoordinatorConfig", "ClusterFailed", "Coordinator",
           "coordinator"]


class ClusterFailed(RuntimeError):
    """Unrecoverable: too few hosts survive, or recoveries exhausted."""


@dataclass(frozen=True)
class CoordinatorConfig:
    """Shape of the supervised ingest run (checkpoint/lease knobs in
    :class:`repro.runtime.ft.FTConfig`).

    ``hosts`` logical workers share the edge stream round-robin by block;
    with ``backend="sharded"`` the engine runs one register shard per
    live host and reshards to the survivor count on eviction. ``block``
    is the ingest granularity (edges per block) — also the heartbeat
    tick. A host whose heartbeat is older than ``lease_blocks`` ticks is
    evicted. ``ckpt_every`` counts blocks between async checkpoints.
    ``min_hosts``/``max_recoveries`` bound how much failure the run
    absorbs before raising :class:`ClusterFailed`.
    """

    hosts: int = 2
    block: int = 1024
    ckpt_every: int = 2
    lease_blocks: int = 2
    min_hosts: int = 1
    max_recoveries: int = 8


class Coordinator:
    """Supervised streaming ingest with eviction + elastic recovery.

    Construct with the full edge array and the same engine coordinates
    ``engine.build`` takes, then call :meth:`run`. Faults come from a
    :class:`repro.runtime.faults.FaultInjector`; without one the loop
    degrades to plain checkpointed ingest. ``replicate`` optionally
    installs a hot-row replica set before ingest so placement survives
    recovery (the id set rides the checkpoint leaf from DESIGN.md §12).
    """

    def __init__(self, edges, n: int, cfg=None, *, ft: FTConfig,
                 config: CoordinatorConfig | None = None,
                 faults: FaultInjector | None = None,
                 backend: str = "local", impl: str | None = None,
                 layout: str | None = None, family: str | None = None,
                 replicate=None):
        self.edges = np.asarray(edges)
        self.n = int(n)
        self.cfg = cfg
        self.ft = ft
        self.cc = config or CoordinatorConfig()
        self.injector = faults or FaultInjector()
        self.backend = backend
        self.impl = impl
        self.layout = layout
        self.family = family
        self.replicate_ids = replicate
        self.alive = list(range(self.cc.hosts))
        self.evicted: list[int] = []
        self.ckpt = AsyncCheckpointer(ft.ckpt_dir, keep=ft.keep)
        self.watchdog = StragglerWatchdog(
            factor=ft.straggler_factor, alpha=ft.ewma_alpha,
            warmup=ft.warmup_steps,
            on_straggler=self._on_straggler)
        self._last_beat: dict[int, int] = {}
        self.stats = {
            "hosts": self.cc.hosts, "hosts_alive": self.cc.hosts,
            "hosts_evicted": [], "heartbeats_seen": 0, "evictions": 0,
            "recoveries": 0, "last_recovery_ms": None,
            "checkpoints_written": 0, "blocks_done": 0,
            "blocks_replayed": 0, "straggler_steps": 0, "retries": 0,
        }

    # ------------------------------------------------------------ pieces
    def _on_straggler(self, dt: float, ewma: float) -> None:
        """Watchdog callback: count the slow block (eviction stays lease-based)."""
        self.stats["straggler_steps"] += 1

    def _engine_kwargs(self) -> dict:
        """Engine coordinates for the *current* live-host count."""
        kw = {"backend": self.backend, "impl": self.impl,
              "layout": self.layout, "family": self.family}
        if self.backend == "sharded":
            kw["shards"] = len(self.alive)
        return kw

    def _fresh_engine(self):
        """Empty engine (no usable checkpoint to restore from)."""
        eng = engine.open(self.n, self.cfg, **self._engine_kwargs())
        if self.replicate_ids is not None:
            eng.replicate(self.replicate_ids)
        return eng

    def _checkpoint(self, eng, step: int) -> None:
        """Initiate one async engine-format checkpoint at ``step``."""
        tree, extra = eng.checkpoint_state()
        self.ckpt.save(step, tree, extra=extra)
        self.stats["checkpoints_written"] += 1

    def _reset_leases(self, block: int) -> None:
        """Fresh lease for every survivor as of ``block``."""
        self._last_beat = {h: block - 1 for h in self.alive}

    def _beat(self, block: int) -> None:
        """Collect this tick's heartbeats, then enforce leases."""
        for h in self.alive:
            if self.injector.heartbeat_visible(h, block):
                self._last_beat[h] = block
                self.stats["heartbeats_seen"] += 1
        for h in self.alive:
            if block - self._last_beat[h] >= self.cc.lease_blocks:
                raise HostLost(h, block, reason="lease expired")

    def _apply(self, eng, chunk: np.ndarray, host: int, block: int) -> None:
        """Ingest one block with the ft retry policy around transients."""
        for attempt in range(self.ft.max_retries + 1):
            try:
                eng.ingest(chunk)
                return
            except HostLost:
                raise
            except Exception:
                if attempt == self.ft.max_retries:
                    raise
                self.stats["retries"] += 1

    # ------------------------------------------------------- control loop
    def _ingest_from(self, eng, cursor: int):
        """Drive blocks [cursor/block, end); raises HostLost on failures."""
        block = self.cc.block
        total = math.ceil(len(self.edges) / block) if len(self.edges) else 0
        b = cursor // block
        while b < total:
            owner = self.alive[b % len(self.alive)]
            self.injector.tick(b)
            if self.injector.is_dead(owner):
                raise HostLost(owner, b, reason="killed")
            t0 = time.monotonic()
            d = self.injector.delay(owner, b)
            if d:  # injected straggle is part of the observed step time
                time.sleep(d)
            self._apply(eng, self.edges[b * block:(b + 1) * block],
                        owner, b)
            self.watchdog.observe(time.monotonic() - t0)
            self._beat(b)
            self.stats["blocks_done"] += 1
            if (b + 1) % self.cc.ckpt_every == 0:
                self._checkpoint(eng, step=b)
            b += 1
        return eng

    def _recover(self, err: HostLost):
        """Evict, restore the newest complete manifest, return (eng, cursor)."""
        t0 = time.monotonic()
        self.ckpt.wait()  # an in-flight complete write may be the newest
        dead = [h for h in self.alive if self.injector.is_dead(h)]
        if err.host in self.alive and err.host not in dead:
            dead.append(err.host)  # lease-expired, not fault-killed
        for h in dead:
            self.alive.remove(h)
            self.evicted.append(h)
            self.injector.fence(h)
        self.stats["evictions"] += len(dead)
        self.stats["hosts_alive"] = len(self.alive)
        self.stats["hosts_evicted"] = list(self.evicted)
        self.stats["recoveries"] += 1
        if len(self.alive) < self.cc.min_hosts:
            raise ClusterFailed(
                f"{len(self.alive)} hosts survive, need {self.cc.min_hosts}")
        if self.stats["recoveries"] > self.cc.max_recoveries:
            raise ClusterFailed(
                f"exceeded max_recoveries={self.cc.max_recoveries}")
        step = latest_step(self.ft.ckpt_dir)
        if step is None:
            eng, cursor = self._fresh_engine(), 0
        else:
            eng = engine.load(self.ft.ckpt_dir, step=step,
                              **self._engine_kwargs())
            cursor = eng.m
        self._reset_leases(cursor // self.cc.block)
        self.stats["blocks_replayed"] += max(
            0, err.block - cursor // self.cc.block)
        self.stats["last_recovery_ms"] = (time.monotonic() - t0) * 1e3
        return eng, cursor

    def run(self):
        """Ingest the whole stream under supervision; return the engine.

        Restore-latest on entry (restart-exact semantics inherited from
        ``train_loop``), then loop ingest -> recover until the stream is
        exhausted. Ends with a final synchronous checkpoint so the run's
        result is durable. ``self.stats`` holds the runtime counters the
        serving layer surfaces.
        """
        start = latest_step(self.ft.ckpt_dir)
        if start is None:
            eng, cursor = self._fresh_engine(), 0
        else:
            eng = engine.load(self.ft.ckpt_dir, step=start,
                              **self._engine_kwargs())
            cursor = eng.m
        self._reset_leases(cursor // self.cc.block)
        while True:
            try:
                self._ingest_from(eng, cursor)
                break
            except HostLost as e:
                eng, cursor = self._recover(e)
        last_block = max(0, math.ceil(len(self.edges) / self.cc.block) - 1)
        self._checkpoint(eng, step=last_block)
        self.ckpt.wait()
        self.stats["straggler_steps"] = self.watchdog.straggler_steps
        return eng


def coordinator(edges, n: int, cfg=None, *, ft: FTConfig,
                config: CoordinatorConfig | None = None,
                faults: FaultInjector | None = None, backend: str = "local",
                impl: str | None = None, layout: str | None = None,
                family: str | None = None, replicate=None):
    """Run a supervised ingest end to end; returns ``(engine, stats)``.

    The functional entry point ``runtime.ft.coordinator`` now delegates
    to — see :class:`Coordinator` for the protocol and DESIGN.md §14 for
    the invariants (restore ordering, lease policy, resume cursor).
    """
    c = Coordinator(edges, n, cfg, ft=ft, config=config, faults=faults,
                    backend=backend, impl=impl, layout=layout,
                    family=family, replicate=replicate)
    eng = c.run()
    return eng, c.stats


def _smoke() -> int:
    """Kill-one-host CI smoke: recover and match an uninterrupted build.

    Builds a small random graph on a sharded mesh of up to 4 hosts (one
    per visible device), kills host 1 mid-stream, and asserts the
    recovered engine's degrees, union and both ring-schedule neighborhood
    curves are bit-identical to a build that never failed. Prints the
    runtime stats block and ``FAILOVER_SMOKE_OK`` on success.
    """
    import json
    import tempfile

    import jax

    from repro.runtime.faults import KillHost

    hosts = min(4, jax.device_count())
    if hosts < 2:
        raise SystemExit(
            f"--smoke kills one of several hosts: needs >= 2 devices, "
            f"{jax.device_count()} visible")
    rng = np.random.default_rng(7)
    n, m = 300, 4096
    edges = rng.integers(0, n, size=(m, 2), dtype=np.int64)
    with tempfile.TemporaryDirectory() as d:
        ft = FTConfig(ckpt_dir=os.path.join(d, "ckpt"), keep=3)
        cc = CoordinatorConfig(hosts=hosts, block=256, ckpt_every=2)
        eng, stats = coordinator(
            edges, n, ft=ft, config=cc, backend="sharded",
            faults=FaultInjector(faults=(KillHost(host=1, at_block=8),)),
            replicate=[0, 1, 2, 3])
        ref = engine.build(edges, n, backend="sharded", shards=hosts)
        assert stats["recoveries"] == 1 and stats["evictions"] == 1, stats
        assert np.array_equal(np.asarray(eng.degrees()),
                              np.asarray(ref.degrees())), "degrees diverge"
        assert np.array_equal(
            np.asarray(eng.union_size([[0, 1, 2]])),
            np.asarray(ref.union_size([[0, 1, 2]]))), "union diverges"
        for sched in ("ring", "ring_overlap"):
            a = eng.neighborhood(3, schedule=sched)
            b = ref.neighborhood(3, schedule=sched)
            assert all(np.array_equal(np.asarray(x), np.asarray(y))
                       for x, y in zip(a, b)), f"neighborhood({sched})"
        print(json.dumps(stats, indent=2))
    print("FAILOVER_SMOKE_OK")
    return 0


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        jaxenv.use_compile_cache()
        sys.exit(_smoke())
    print(__doc__)
