"""Worker process: runs a subset of the TaskGraph's channels.

The reference spreads channels across Ray TaskManager actors
(pyquokka/core.py:54-151); here each worker process owns a set of
(actor, channel) pairs, reuses the embedded Engine's task handlers verbatim
against a ControlStoreClient, keeps a LOCAL BatchCache served over the socket
data plane, and routes pushes by the channel-location table (CLT).

Recovery: on a peer's death the coordinator mails surviving workers
("adopt", actor, channel) messages; the adopter replays checkpoint + tape +
HBQ with the same Engine recovery code the embedded runtime uses.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Tuple


from quokka_tpu import obs
from quokka_tpu.runtime.cache import BatchCache
from quokka_tpu.runtime.dataplane import DataPlaneClient, serve_cache, table_to_ipc
from quokka_tpu.runtime.engine import ActorInfo, Engine
from quokka_tpu.runtime.state import WorkerState
from quokka_tpu.runtime.store_service import ControlStoreClient



class WorkerGraph:
    """Duck-typed TaskGraph for Engine: store client + local cache + actors."""

    def __init__(self, store, cache, actors, exec_config, hbq, ckpt_dir,
                 query_id=None):
        self.store = store
        self.cache = cache
        self.actors = actors
        self.exec_config = exec_config
        self.hbq = hbq
        self.ckpt_dir = ckpt_dir
        # distributed sessions run one query per served store today, so this
        # stays None there; the engine's query tagging/namespacing keys off it
        self.query_id = query_id


def _actors_from_spec(spec: Dict) -> Dict[int, ActorInfo]:
    actors = {}
    for aid, d in spec["actors"].items():
        info = ActorInfo(aid, d["kind"], d["channels"], d["stage"], d["sorted_actor"])
        info.reader = d["reader"]
        info.executor_factory = d["factory"]
        info.targets = d["targets"]
        info.source_streams = d["source_streams"]
        info.sorted_by = d["sorted_by"]
        info.predicate = d["predicate"]
        info.projection = d["projection"]
        info.blocking = d["blocking"]
        info.channel_major = d.get("channel_major", False)
        info.placement = d.get("placement")
        info.blocking_dataset = None
        actors[aid] = info
    return actors


class Worker(Engine):
    # never rewind a LIVE peer-owned channel from this process: the owner's
    # in-flight dispatch would race the rewind (engine._maybe_force_
    # producer_rewind) — distributed loss escalation stays with the
    # coordinator's co-dead planning + the loud wait-deadline
    _allow_forced_rewind = False

    def __init__(self, spec: Dict, store, cache: BatchCache, worker_id: int,
                 owned: Dict[int, List[int]], hbq=None):
        actors = _actors_from_spec(spec)
        if hbq is None and spec["hbq_path"]:
            hbq = _worker_hbq(spec, worker_id)
        g = WorkerGraph(store, cache, actors, spec["exec_config"], hbq,
                        spec["ckpt_dir"], query_id=spec.get("query_id"))
        self.worker_id = worker_id
        self._init_latency_hists(g)
        self.owned = {a: set(chs) for a, chs in owned.items()}
        self._peers: Dict[int, DataPlaneClient] = {}
        self._peer_addrs: Dict[int, Tuple[str, int]] = {}
        self._clt: Dict[Tuple[int, int], int] = {}
        # Engine.__init__ builds every exec channel; do it owned-only
        self.g = g
        self.store = store
        self.cache = cache
        self.max_batches = g.exec_config.get("max_pipeline_batches", 8)
        self.execs = {}
        self._partition_fns = {}
        for info in actors.values():
            if info.kind == "exec":
                for ch in self.owned.get(info.id, ()):
                    self.execs[(info.id, ch)] = info.executor_factory()
        # AST/SAT are write-once at graph build: snapshot from the spec so the
        # scheduling hot loop never round-trips them through the store
        self._stages_cache = {a.id: a.stage for a in actors.values()}
        self._sorted_cache = {a.id for a in actors.values() if a.sorted_actor}
        self._cm_cache = {
            a.id for a in actors.values() if getattr(a, "channel_major", False)
        }

    def _actor_stages(self):
        return self._stages_cache

    def _sorted_actors(self):
        return self._sorted_cache

    def _channel_major_actors(self):
        return self._cm_cache

    # -- routing --------------------------------------------------------------
    def _refresh_clt(self):
        self._clt = dict(self.store.titems("CLT"))

    def _peer(self, worker_id: int) -> DataPlaneClient:
        cli = self._peers.get(worker_id)
        if cli is None:
            addr = self._peer_addrs.get(worker_id)
            if addr is None:
                self._peer_addrs = dict(self.store.get("worker_addrs") or {})
                addr = self._peer_addrs[worker_id]
            cli = self._peers[worker_id] = DataPlaneClient(addr)
        return cli

    def _cache_put(self, name, part):
        tgt = (name[3], name[5])
        deadline = time.time() + 30
        compacted = False
        while True:
            owner = self._clt.get(tgt)
            if owner is None:
                self._refresh_clt()
                owner = self._clt[tgt]
            if owner == self.worker_id:
                self.cache.put(name, part)
                return
            try:
                if not compacted and part.padded_len > (1 << 16):
                    # remote put serializes the batch whole: a masked-view
                    # partition would ship the full PARENT padded buffers
                    # (fan-out times the bytes) — compact before the wire,
                    # same discipline as the spill worker (_spill_one)
                    from quokka_tpu.ops import kernels

                    part = kernels.compact(part)
                    compacted = True
                self._peer(owner).put(name, part, part.sorted_by)
                return
            except (ConnectionError, OSError):
                # peer died mid-push: drop the stale client and wait for the
                # coordinator to repoint the channel in CLT
                self._peers.pop(owner, None)
                if time.time() > deadline:
                    raise
                time.sleep(0.2)
                self._refresh_clt()

    def _result_append(self, info, channel, seq, table):
        self.store.result_append(info.id, channel, seq, table_to_ipc(table))

    # -- HBQ across workers ---------------------------------------------------
    # Spill is producer-local (each worker's PRIVATE dir — no shared
    # filesystem assumed); recovery aggregates this worker's HBQ with every
    # reachable peer's, served over the data plane.  An unreachable peer is
    # negative-cached for a while (a dead REMOTE host otherwise costs a full
    # connect timeout per probe), and per-target holder maps are TTL-cached
    # so resolving N lost objects costs ~P listing calls, not N*P probes.
    _PEER_DOWN_TTL = 15.0
    _HOLDER_TTL = 1.0

    def _iter_peer_clients(self, refresh_addrs: bool = True):
        if refresh_addrs:
            now = time.time()
            if now - getattr(self, "_addrs_at", 0) > 2.0:
                self._peer_addrs = dict(self.store.get("worker_addrs") or {})
                self._addrs_at = now
        down = getattr(self, "_peers_down", None)
        if down is None:
            down = self._peers_down = {}
        for w in sorted(self._peer_addrs):
            if w == self.worker_id:
                continue
            if time.time() < down.get(w, 0):
                continue
            try:
                yield w, self._peer(w)
            except (ConnectionError, OSError):
                self._peers.pop(w, None)
                down[w] = time.time() + self._PEER_DOWN_TTL

    def _hbq_holders(self, tgt: Tuple[int, int]):
        """name -> peer worker id, one listing RPC per live peer, TTL-cached
        (listings grow while co-dead producers replay, so the cache is
        deliberately short-lived)."""
        cache = getattr(self, "_holder_cache", None)
        if cache is None:
            cache = self._holder_cache = {}
        hit = cache.get(tgt)
        if hit is not None and time.time() - hit[0] < self._HOLDER_TTL:
            return hit[1]
        holders = {}
        for w, cli in self._iter_peer_clients():
            try:
                for name in cli.hbq_names_for_target(*tgt):
                    holders[name] = w
            except (ConnectionError, OSError):
                self._peers.pop(w, None)
                self._peers_down[w] = time.time() + self._PEER_DOWN_TTL
        cache[tgt] = (time.time(), holders)
        return holders

    def _hbq_names_for_target(self, tgt_actor: int, tgt_ch: int):
        names = set(self.g.hbq.names_for_target(tgt_actor, tgt_ch))
        names.update(self._hbq_holders((tgt_actor, tgt_ch)))
        return sorted(names)

    def _hbq_contains(self, name):
        if self.g.hbq is not None and self.g.hbq.contains(name):
            return True
        return tuple(name) in self._hbq_holders((name[3], name[5]))

    def _hbq_fetch(self, name):
        table = self.g.hbq.get(name)
        if table is not None:
            return table
        w = self._hbq_holders((name[3], name[5])).get(tuple(name))
        if w is None:
            return None
        try:
            return self._peer(w).hbq_get(name)
        except (ConnectionError, OSError):
            self._peers.pop(w, None)
            self._peers_down[w] = time.time() + self._PEER_DOWN_TTL
            return None

    # -- recovery adoption ----------------------------------------------------
    def _adopt(self, actor: int, channel: int, choice=None):
        """Take over a failed peer's channel: the shared Engine recovery path
        (checkpoint + tape + HBQ replay) against this worker's local cache.
        `choice` is the coordinator's rewind-planner checkpoint selection."""
        obs.RECORDER.record("adopt", f"a{actor}c{channel}",
                            choice=repr(choice))
        # flush barrier: adoption replays from HBQ listings (ours included);
        # our own pending async spills must be durable first
        self._flush_spills()
        self.owned.setdefault(actor, set()).add(channel)
        self._recover_channel(actor, channel, choice=choice)

    # -- observability --------------------------------------------------------
    _FLIGHT_SHIP_EVERY = 0.5  # seconds between incremental event shipments

    def _worker_state(self, phase: str, now: float) -> WorkerState:
        return WorkerState(
            worker_id=self.worker_id,
            phase=phase,
            task=getattr(self, "_obs_task", None),
            last_progress=getattr(self, "_obs_last_progress", 0.0),
            queue_hint=self.cache.size(),
            events_seq=getattr(self, "_obs_shipped_seq", -1),
            dropped=obs.RECORDER.dropped,
            ts=now,
        )

    def _ship_flight(self) -> None:
        """Ship the flight-recorder events recorded since the last shipment
        (incremental: the full ring would be hundreds of KB at 2 Hz)."""
        since = getattr(self, "_obs_shipped_seq", -1)
        evs = obs.RECORDER.snapshot(since=since)
        if evs:
            self.store.flight_append(self.worker_id, evs)
            self._obs_shipped_seq = evs[-1][0]

    # -- main loop ------------------------------------------------------------
    def run_worker(self, heartbeat_every: float = 0.2):
        # QK_SANITIZE=1: the loop beats a watchdog; a dispatch that wedges
        # (lock/pipe deadlock) stops the beats, and the watchdog dumps every
        # thread's stack and kills this process — the coordinator then fails
        # the run in seconds instead of hanging to its timeout
        watchdog = getattr(self, "_watchdog", None)
        rec = obs.RECORDER
        rec.record("worker.start", f"worker-{self.worker_id}")
        # startup barrier: wait until every worker's data-plane address is
        # registered, or the first push to a late-starting peer would fail
        expected = self.store.get("expected_workers")
        t0 = time.time()
        while expected:
            addrs = self.store.get("worker_addrs") or {}
            if len(addrs) >= expected:
                self._peer_addrs = {int(k): tuple(v) for k, v in addrs.items()}
                rec.record("worker.barrier", f"{len(addrs)} peers registered")
                break
            if self.store.get("SHUTDOWN"):
                return
            if time.time() - t0 > 120:
                raise TimeoutError("peer workers never registered")
            self.store.heartbeat(self.worker_id,
                                 self._worker_state("barrier", time.time()))
            if watchdog is not None:
                watchdog.beat()
            time.sleep(0.05)
        last_hb = 0.0
        last_ship = 0.0
        dbg = os.environ.get("QUOKKA_DEBUG_WORKER")
        dbg_at = time.time()
        self._obs_last_progress = time.time()
        actors = sorted(self.g.actors.values(), key=lambda a: (a.stage, a.id))
        phase = "run"
        while True:
            now = time.time()
            if watchdog is not None:
                watchdog.beat()
            if now - last_hb >= heartbeat_every:
                self.store.heartbeat(self.worker_id,
                                     self._worker_state(phase, now))
                rec.record("hb", f"worker-{self.worker_id}")
                last_hb = now
            if now - last_ship >= self._FLIGHT_SHIP_EVERY:
                self._ship_flight()
                last_ship = now
            for msg in self.store.mailbox_drain(self.worker_id):
                if msg[0] == "adopt":
                    self._refresh_clt()
                    self._adopt(msg[1], msg[2],
                                choice=msg[3] if len(msg) > 3 else None)
            if self.store.get("SHUTDOWN"):
                rec.record("worker.shutdown", f"worker-{self.worker_id}")
                self._ship_flight()
                return
            stage = self.store.get("STAGE", 0)
            progress = False
            popped = []
            for info in actors:
                chans = self.owned.get(info.id)
                if not chans:
                    continue
                if info.kind == "input" and info.stage > stage:
                    continue
                task = self.store.ntt_pop(info.id, list(chans),
                                          self.worker_id)
                if task is None:
                    continue
                if dbg:
                    popped.append((info.id, task.name,
                                   getattr(task, "channel", None)))
                # remembered in the heartbeat payload so the coordinator
                # can name the in-flight task even mid-dispatch
                self._obs_task = (task.name, info.id,
                                  getattr(task, "channel", None))
                progress |= self.dispatch_task(task)
            if progress:
                dbg_at = now
                self._obs_last_progress = now
                phase = "run"
            else:
                phase = "idle"
                if dbg and now - dbg_at > 5.0:
                    dbg_at = now
                    obs.diag(
                        f"[worker {self.worker_id}] stalled: owned="
                        f"{ {a: sorted(c) for a, c in self.owned.items()} } "
                        f"popped={popped} "
                        f"cache={self.cache.size()} puttable={self.cache.puttable()}"
                    )
                time.sleep(0.01)


def _worker_hbq(spec: Dict, worker_id: int):
    """Each worker spills into its own PRIVATE subdir of the run's spill
    root — nothing assumes peers can read it from disk (multi-host safe);
    recovery fetches across workers over the data plane instead."""
    from quokka_tpu.runtime.hbq import HBQ

    return HBQ(os.path.join(spec["hbq_path"], f"worker-{worker_id}"))


def worker_main(spec_bytes: bytes, store_addr, worker_id: int, owned):
    """Spawn entry point (module-level for multiprocessing spawn)."""
    import pickle

    # chaos plane: spawned children inherit QK_CHAOS through the environment;
    # the role keys this worker's seeded fault streams apart from (and as
    # reproducibly as) the coordinator's
    from quokka_tpu.chaos import CHAOS

    if CHAOS.enabled:
        CHAOS.set_role(f"worker-{worker_id}")
    spec = pickle.loads(spec_bytes)
    if spec.get("x64"):
        import jax

        jax.config.update("jax_enable_x64", True)
    store = ControlStoreClient(tuple(store_addr))
    w = None
    try:
        cache = BatchCache()
        hbq = _worker_hbq(spec, worker_id) if spec["hbq_path"] else None
        # advertise the address peers can actually reach: the local IP of the
        # socket we used to reach the coordinator (loopback stays loopback;
        # a cross-host connection yields this machine's routable IP, and the
        # cache binds all interfaces in that case)
        my_ip = store._rpc._sock.getsockname()[0]
        bind = "127.0.0.1" if my_ip.startswith("127.") else "0.0.0.0"
        server = serve_cache(cache, host=bind, hbq=hbq)
        store.set(f"worker_addr:{worker_id}", (my_ip, server.address[1]))
        # the coordinator merges individual keys into 'worker_addrs' itself
        store.heartbeat(worker_id)
        w = Worker(spec, store, cache, worker_id, owned, hbq=hbq)
        from quokka_tpu.analysis import sanitize

        w._watchdog = sanitize.start_watchdog(f"worker-{worker_id}")
        try:
            w.run_worker()
            w._flush_emits()
        finally:
            if w._watchdog is not None:
                w._watchdog.stop()
            try:
                w._flush_metrics()
            except Exception:
                pass  # a dead coordinator store must not block shutdown
            w._shutdown_prefetch()
            w._shutdown_emitter()
            w._shutdown_spill()
            server.close()
    except Exception:
        import traceback

        # ship the traceback to the coordinator — a spawned child's stderr is
        # otherwise invisible and the run would stall until timeout
        try:
            store.set(f"worker_error:{worker_id}", traceback.format_exc())
            # unshipped flight-recorder events too (only those PAST the
            # incremental shipper's high-water mark — re-shipping the tail
            # would duplicate slices in the merged timeline): the stall
            # dump then shows what this worker did right up to the crash
            since = getattr(w, "_obs_shipped_seq", -1) if w is not None else -1
            evs = obs.RECORDER.snapshot(since=since, last_n=256)
            if evs:
                store.flight_append(worker_id, evs)
        except Exception:
            pass
        raise
    finally:
        store.close()


def _connect_store(addr, deadline: Optional[float]):
    """Connect with retry: the coordinator's store may not be serving yet
    (daemons can be launched before the first query), or may be between
    query sessions in --persist mode.  deadline=None retries forever.
    A token mismatch is deterministic and fails fast, never retried."""
    from quokka_tpu.runtime.rpc import RpcAuthError

    while True:
        try:
            return ControlStoreClient(addr)
        except RpcAuthError:
            raise
        except (ConnectionRefusedError, ConnectionError, OSError, TimeoutError):
            if deadline is not None and time.time() > deadline:
                raise
            time.sleep(0.5)


def _serve_one_session(addr, worker_id: int, join_timeout: float,
                       served=None) -> bool:
    """Join the store at addr, fetch plan + ownership, run until SHUTDOWN.
    Returns False when no plan appeared within join_timeout (nothing ran).

    `served` (persist mode): set of session ids this daemon has already
    joined.  A session is joined AT MOST ONCE — if the daemon crashed out of
    it, the coordinator has declared it dead and adopted its channels on a
    survivor; rejoining with the original ownership map would split-brain
    (two workers taping the same channels)."""
    store = _connect_store(addr, time.time() + join_timeout)
    try:
        deadline = time.time() + join_timeout
        spec_bytes = None
        owned = None
        sid = None
        while time.time() < deadline:
            if store.get("SHUTDOWN"):
                return False  # tail of an already-finished session
            sid = store.get("session_id")
            if served is not None and sid is not None and sid in served:
                return False  # already joined (and possibly crashed out of)
            spec_bytes = store.get("spec")
            owned = store.get(("owned", worker_id))
            if spec_bytes is not None and owned is not None:
                if sid is None:
                    # session_id is published BEFORE spec (run_distributed),
                    # so it is guaranteed visible once spec is — this re-read
                    # closes the sid-then-spec interleave that would
                    # otherwise run a session without recording it in
                    # `served` (split-brain on crash-and-reconnect)
                    sid = store.get("session_id")
                    if served is not None and sid in served:
                        return False
                break
            time.sleep(0.2)
    finally:
        store.close()
    if spec_bytes is None or owned is None:
        return False
    if served is not None:
        if sid is None:
            return False  # store never published a session id: do not run
        served.add(sid)
    worker_main(spec_bytes, addr, worker_id, owned)
    return True


def main(argv=None):
    """Standalone worker for multi-host deployments: join a coordinator's
    served store, fetch the plan + channel ownership, and run.

        python -m quokka_tpu.runtime.worker --store HOST:PORT --worker-id K \
            [--persist]

    The coordinator must be started with external_workers > K so K's channels
    get assigned (runtime/distributed.run_distributed).  --persist keeps the
    daemon alive across queries: each QuokkaContext query serves a fresh
    store session on the same port; the daemon reconnects and serves each in
    turn until killed (the deployment mode QuokkaClusterManager.start_cluster
    launches).  The daemon authenticates with QUOKKA_RPC_TOKEN
    (runtime/rpc.py)."""
    import argparse

    p = argparse.ArgumentParser(description=main.__doc__)
    p.add_argument("--store", required=True, help="coordinator HOST:PORT")
    p.add_argument("--worker-id", type=int, required=True)
    p.add_argument("--persist", action="store_true",
                   help="serve query sessions forever (daemon mode)")
    args = p.parse_args(argv)
    host, port = args.store.rsplit(":", 1)
    addr = (host, int(port))
    if not args.persist:
        if not _serve_one_session(addr, args.worker_id, join_timeout=120):
            raise TimeoutError(
                f"coordinator at {args.store} never published a plan for "
                f"worker {args.worker_id} (was it started with "
                "external_workers > this id?)"
            )
        return
    from quokka_tpu.runtime.rpc import RpcAuthError

    served: set = set()
    auth_failures = 0
    while True:
        try:
            if _serve_one_session(addr, args.worker_id, join_timeout=10,
                                  served=served):
                auth_failures = 0
        except RpcAuthError:
            # A server that closes mid-handshake is indistinguishable from a
            # token rejection (the server deliberately reveals nothing), and
            # a coordinator tearing down a finished session produces exactly
            # that close.  Retry a couple of times; a real token mismatch is
            # deterministic and still dies loudly.
            auth_failures += 1
            if auth_failures >= 3:
                raise
        except (ConnectionError, OSError, TimeoutError, EOFError):
            pass  # session ended mid-flight (coordinator closed); rejoin
        except Exception:
            import traceback

            traceback.print_exc()  # session crashed; daemon stays up
        time.sleep(0.3)


if __name__ == "__main__":
    main()
