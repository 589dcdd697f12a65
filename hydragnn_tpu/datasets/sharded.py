"""Non-shared-filesystem data plane: per-host packed shards + TCP sample
exchange — the role of the reference's DDStore
(``hydragnn/utils/datasets/distdataset.py:72-367``: each rank materializes
only its window and serves remote ``get()`` fetches over MPI RMA windows).

``GlobalShuffleStore`` (``packed.py``) assumes every host can mmap the SAME
packed file — a shared filesystem or pre-replicated copy. When each host
instead holds only its own shard on local disk, ``ShardedStore`` fills the
gap:

* host ``h`` owns global indices ``[start_h, stop_h)`` backed by its local
  ``PackedDataset`` shard;
* a per-host ``ShardServer`` thread answers batched index fetches over TCP
  (the MPI-RMA → TCP translation; one request per owner per batch);
* the address book (host, port, index range) is exchanged once through
  ``jax.experimental.multihost_utils.process_allgather`` when running under
  ``jax.distributed`` — or passed explicitly (``peers=``) for tests;
* reads of any global index then work from every host: local → zero-copy
  mmap, remote → fetch + bounded LRU cache.

Feed the store straight to ``GraphLoader(..., rank, world, shuffle=True)``:
each host's per-epoch stride of the shared global permutation now spans the
WHOLE corpus (the DDStore property), fetching the ~(world-1)/world
non-local samples from their owners.

Elastic tier (replication + failover): peer ranges may OVERLAP — with
``replication_factor=R`` every range is served by R owners holding mirror
shards, a dead/slow owner fails over to a replica instead of stalling the
fleet, dead peers are quarantined with re-probe backoff (a background
prober pings them over the same protocol and lifts the quarantine when the
host returns), and watchdog deadlines bracket every replica round-trip so
even a byte-dribbling peer cannot park an epoch. See the ``ShardedStore``
docstring and README "Elastic data plane".

Wire format is a length-prefixed binary array framing (name + dtype str +
shape + raw bytes per array): decode is ``np.frombuffer`` views — no
pickle anywhere, and object dtypes are rejected on both ends, so a
malicious peer cannot execute code on load. The trust model is the
reference's — an internal cluster network, like its MPI windows. The
optional ``auth_token`` and bindable listen interface protect against
MISCONFIGURATION (two jobs sharing a fabric, a peer dialing the wrong
port), not against a network attacker: the token travels plaintext over
unencrypted TCP and is replayable. Genuinely untrusted networks need
transport security (TLS/WireGuard) underneath, same as MPI would.

The transport itself (framing, codec, auth check, pooled sockets,
watchdog-bracketed round-trips, quarantine clock) lives in
``hydragnn_tpu.utils.wire`` — ONE implementation shared with the fleet
serving tier (``serve/fleet``), factored out of this module where PR 4
grew it. This module keeps the data-plane policy: shard ownership,
replica failover, the re-probe prober, the sample cache.
"""

from __future__ import annotations

import dataclasses
import socket
import threading
import time
import warnings
import weakref
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..graphs.graph import GraphSample
from ..utils import wire
from ..utils.wire import (
    ConnPool as _ConnPool,  # noqa: F401  (back-compat alias)
    HealthTable,
    RoundTripper,
    WireServer,
    check_pong,
)
from .packed import PackedDataset

# back-compat aliases: the wire protocol grew here (PR 4) and tests/tools
# import these by their original private names
_pack_arrays = wire.pack_arrays
_unpack_arrays = wire.unpack_arrays
_send_msg = wire.send_msg
_recv_msg = wire.recv_msg
_recv_exact = wire.recv_exact
_sample_to_arrays = wire.sample_to_arrays
_sample_from_arrays = wire.sample_from_arrays
_copy_sample = wire.copy_sample
_encode_samples = wire.encode_samples
_samples_from_frame = wire.samples_from_frame


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    """Elastic data-plane knobs, single-sourced: these field defaults ARE
    the ``Dataset.store`` config defaults (``config.update_config`` fills
    the block from ``store_config_defaults``) and the ``ShardedStore``
    constructor defaults — one place to tune, nothing to drift.

    * ``replication_factor`` — owners expected per sample range. R=1 is the
      PR 3 data plane (a dead owner stalls the fleet); R>1 lets ``fetch``
      fail over to a live replica and quarantine the dead peer.
    * ``peer_timeout`` — connect/read deadline per peer socket. A peer
      slower than this IS down for failover purposes (gray failures stall
      epochs exactly like crashes; the reference's MPI windows simply hang).
    * ``probe_interval`` — how often the background prober re-pings
      quarantined peers so a recovered host rejoins without operator action.
    * ``quarantine_base_s``/``quarantine_cap_s`` — re-probe backoff window:
      each consecutive failed probe doubles the quarantine, capped so a
      rebooted host waits at most the cap before serving again.
    """

    replication_factor: int = 1
    peer_timeout: float = 120.0
    probe_interval: float = 2.0
    quarantine_base_s: float = 1.0
    quarantine_cap_s: float = 30.0


def store_config_defaults() -> dict:
    """``{config key: default}`` for the ``Dataset.store`` block. EVERY
    ``StoreConfig`` field is a config key, so the mapping is derived from
    ``dataclasses.fields`` — a hand-maintained key tuple would let a future
    field silently drop out of the schema/apply_config plumbing."""
    return {f.name: f.default for f in dataclasses.fields(StoreConfig)}


# Live ShardServer registry (creation order, weakly held): the chaos
# harness's ``dead_shard``/``slow_peer`` faults need a handle on "one of
# the running shard servers" without threading store objects through the
# train loop's fault hooks.
_LIVE_SERVERS: "weakref.WeakValueDictionary[int, ShardServer]" = (
    weakref.WeakValueDictionary()
)
_LIVE_SERVERS_SEQ = [0]
_LIVE_SERVERS_LOCK = threading.Lock()


def live_servers() -> "list[ShardServer]":
    """Currently-running ShardServers in this process, creation order."""
    with _LIVE_SERVERS_LOCK:
        items = sorted(_LIVE_SERVERS.items())
    return [srv for _, srv in items if not srv.closed]


class ShardServer(WireServer):
    """Threaded TCP server answering batched sample fetches from the local
    shard (on the shared ``utils.wire`` transport — auth, ping, instant
    dead-host ``close()``, and chaos ``set_delay`` live in ``WireServer``).
    Request: a ``pack_arrays`` frame {"idx": int64[k] LOCAL indices,
    "range": [start, stop] the GLOBAL range the client believes this server
    owns}; response:
    the encoded samples, or an error record when the range doesn't match —
    a misrouted connection (e.g. every host advertising a loopback address,
    so peers dial their OWN server) must fail LOUDLY, not silently serve
    wrong samples.

    ``host`` restricts the listening interface (default all interfaces —
    the reference's MPI-window trust model on an isolated cluster fabric);
    ``auth_token`` adds a per-request shared-secret check (n=-2 error
    record on mismatch). The token is a MISCONFIGURATION guard — it stops
    a peer from another job/cluster accidentally reading this shard — not
    network security: it travels plaintext and is replayable, so an
    attacker who can sniff the fabric already has the data. The compare is
    ``hmac.compare_digest`` so the guard itself doesn't leak the token
    byte-by-byte through timing. ``_test_delay_s`` is a test hook: a
    per-request sleep that makes fetch-overlap measurements deterministic
    instead of timing-noise-bound."""

    def __init__(self, ds: PackedDataset, start: int, stop: int,
                 host: str = "0.0.0.0", auth_token: str | None = None,
                 port: int = 0, _test_delay_s: float = 0.0):
        self.ds = ds
        self.start, self.stop = int(start), int(stop)
        # port=0 picks an ephemeral port (the default); a fixed port lets a
        # restarted host come back at the address its peers already
        # advertise, so the prober's quarantine-lift finds it
        super().__init__(host=host, port=port, auth_token=auth_token,
                         name="ShardServer", _test_delay_s=_test_delay_s)
        with _LIVE_SERVERS_LOCK:
            _LIVE_SERVERS_SEQ[0] += 1
            _LIVE_SERVERS[_LIVE_SERVERS_SEQ[0]] = self

    def pong_fields(self) -> dict:
        # the prober verifies it is talking to the peer it thinks it is
        # (the advertised range) before lifting a quarantine
        return {"have": np.asarray([self.start, self.stop], np.int64)}

    def handle_frame(self, z: dict) -> bytes | dict:
        want = z.get("range")
        if want is not None and (
            int(want[0]) != self.start or int(want[1]) != self.stop
        ):
            return {
                "n": np.asarray(-1, np.int64),
                "have": np.asarray([self.start, self.stop], np.int64),
            }
        if "sizes" in z:
            # size-table op: (num_nodes, num_edges) for the whole shard
            # straight from the count index — bucket planning never pulls
            # sample content
            return {
                "n": np.asarray(0, np.int64),
                "sizes": self.ds.sample_sizes(range(self.stop - self.start)),
            }
        return _encode_samples([self.ds[int(i)] for i in z["idx"]])


class ShardedStore:
    """Global-index Sequence over per-host shards (see module docstring).

    ``peers``: list over ranks of ``(host, port, start, stop)``. When None,
    exchanged via ``multihost_utils.process_allgather`` (requires
    ``jax.distributed`` to be initialized).

    Elastic data plane (replication + failover): peer ranges may OVERLAP —
    with ``replication_factor=R`` every sample range is advertised by R
    owners (each holding a mirror copy of the range in its local shard
    file), and a remote fetch walks the owners in locality-preferring order
    (healthy replicas first, rotated per client so load spreads; quarantined
    peers last, as a final resort). A connect/timeout failure fails over to
    the next replica instead of raising, quarantines the dead peer (its
    pooled sockets are evicted, re-probe backoff doubles up to a cap), and a
    background prober pings quarantined peers — piggybacked on the fetch
    protocol — so a recovered host rejoins without operator action. A
    watchdog deadline brackets every replica round-trip: a byte-dribbling
    peer that never trips the per-``recv`` socket timeout is forcibly
    disconnected and quarantined rather than stalling the epoch. Only
    transport faults fail over; protocol errors (auth mismatch, misroute,
    server-side exception) stay loud — a *reachable but wrong* peer is a
    configuration bug replicas must not paper over.
    """

    def __init__(
        self,
        shard_path: str,
        start: int,
        stop: int,
        peers: list[tuple[str, int, int, int]] | None = None,
        cache_size: int = 4096,
        advertise_host: str | None = None,
        bind_host: str = "0.0.0.0",
        auth_token: str | None = None,
        max_idle_conns_per_peer: int = 4,
        replication_factor: int | None = None,
        peer_timeout: float | None = None,
        probe_interval: float | None = None,
        quarantine_base_s: float | None = None,
        quarantine_cap_s: float | None = None,
        _test_delay_s: float = 0.0,
    ):
        self.ds = PackedDataset(shard_path)
        if len(self.ds.subset) != stop - start:
            raise ValueError(
                f"shard {shard_path} holds {len(self.ds.subset)} samples but "
                f"claims global range [{start}, {stop})"
            )
        self.start, self.stop = int(start), int(stop)
        self.server = ShardServer(self.ds, start, stop, host=bind_host,
                                  auth_token=auth_token,
                                  _test_delay_s=_test_delay_s)
        if peers is None:
            peers = self._allgather_peers(advertise_host)
        self.peers = sorted(peers, key=lambda p: (p[2], p[3]))
        self.total = max(p[3] for p in self.peers)
        # coverage check: the UNION of peer spans must cover [0, total)
        # with no gap — overlaps (replicas) are the feature, gaps are fatal
        spans = sorted({(p[2], p[3]) for p in self.peers})
        cursor = 0
        for s0, s1 in spans:
            if s0 > cursor:
                raise ValueError(
                    f"shard ranges leave [{cursor}, {s0}) unserved: {spans}"
                )
            cursor = max(cursor, s1)
        self._auth_token = auth_token
        # elastic knobs, precedence: env flag > constructor-explicit arg >
        # Dataset.store config block (apply_config) > StoreConfig default.
        # Explicit args are REMEMBERED so a later apply_config of a
        # schema-filled block (which carries defaults for every key) can't
        # silently clobber what the caller asked for.
        self._explicit_cfg = {
            key
            for key, val in (
                ("replication_factor", replication_factor),
                ("peer_timeout", peer_timeout),
                ("probe_interval", probe_interval),
                ("quarantine_base_s", quarantine_base_s),
                ("quarantine_cap_s", quarantine_cap_s),
            )
            if val is not None
        }
        d = StoreConfig()
        self.replication_factor = int(
            replication_factor if replication_factor is not None
            else d.replication_factor
        )
        self.peer_timeout = float(
            peer_timeout if peer_timeout is not None else d.peer_timeout
        )
        self.probe_interval = float(
            probe_interval if probe_interval is not None else d.probe_interval
        )
        self.quarantine_base_s = float(
            quarantine_base_s if quarantine_base_s is not None
            else d.quarantine_base_s
        )
        self.quarantine_cap_s = float(
            quarantine_cap_s if quarantine_cap_s is not None
            else d.quarantine_cap_s
        )
        self._apply_env_overrides()
        self._verify_replication()
        # deterministic per-client replica rotation (see _replica_order):
        # clients prefer DIFFERENT replicas so replicated reads spread
        # instead of hammering each range's first-listed owner
        self._rot = (self.start * 2654435761 + self.stop) % (1 << 31)
        # the shared wire client: pooled sockets + token stamping +
        # watchdog-bracketed round-trips (utils.wire.RoundTripper)
        self._rt = RoundTripper(
            self.peer_timeout, auth_token=auth_token,
            max_idle_per_peer=max_idle_conns_per_peer,
        )
        # the lock guards ONLY cache/telemetry bookkeeping; network
        # round-trips run outside it so concurrent fetches overlap
        self._lock = threading.Lock()
        self._cache: OrderedDict[int, GraphSample] = OrderedDict()  # guarded-by: _lock
        self._cache_size = int(cache_size)
        self._sizes: np.ndarray | None = None  # guarded-by: _sizes_lock
        self._sizes_lock = threading.Lock()
        self._executor: ThreadPoolExecutor | None = None  # guarded-by: _lock
        self.remote_fetches = 0  # guarded-by: _lock (audited by tests/bench)
        self.failover_fetches = 0  # guarded-by: _lock (replica re-fetches)
        self.quarantine_events = 0  # guarded-by: _lock (peer-down events)
        # quarantine clock: rank -> {"until", "backoff", "failures"}; a rank
        # is quarantined while now < until AND the entry exists (the prober —
        # or a successful last-resort fetch — removes it). Shared
        # implementation with the fleet router (utils.wire.HealthTable).
        self._health_table = HealthTable(
            self.quarantine_base_s, self.quarantine_cap_s
        )
        self._probe_stop = threading.Event()
        self._probe_thread: threading.Thread | None = None

    @property
    def _pool(self):
        """The per-peer socket pool (tests poke ``_idle``/``timeout``)."""
        return self._rt.pool

    @property
    def _health(self) -> dict:
        return self._health_table.entries

    @property
    def _health_lock(self):
        return self._health_table.lock

    def _apply_env_overrides(self) -> None:
        from ..utils import flags

        env_r = flags.get(flags.REPLICATION)
        if env_r is not None:
            self.replication_factor = int(env_r)
        env_t = flags.get(flags.PEER_TIMEOUT)
        if env_t is not None:
            self.peer_timeout = float(env_t)

    def apply_config(self, cfg: dict) -> None:
        """Apply a ``Dataset.store`` config block (schema-filled defaults)
        to a live store: ``run_training`` calls this so a store constructed
        before the config was loaded still honors it. Knobs the caller set
        EXPLICITLY at construction are kept — the schema fills the block
        with defaults for every key, and letting those overwrite an
        explicit ``replication_factor=2`` would silently disable the
        elastic layer. Env flags keep the last word, matching every other
        HYDRAGNN_* knob."""
        for key in store_config_defaults():
            if key in self._explicit_cfg:
                continue
            if cfg.get(key) is not None:
                setattr(self, key, type(getattr(self, key))(cfg[key]))
        self._apply_env_overrides()
        # the timeout setter also drops the armed watchdog so the next
        # round-trip rebuilds it with the new deadline
        self._rt.timeout = self.peer_timeout
        self._health_table.base_s = self.quarantine_base_s
        self._health_table.cap_s = self.quarantine_cap_s
        self._verify_replication()

    def _verify_replication(self) -> None:
        """Warn when any elementary range has fewer owners than the
        configured replication factor — an under-replicated range is one
        host loss away from stalling the fleet, which is exactly what
        replication_factor > 1 was supposed to prevent."""
        if self.replication_factor <= 1:
            return
        bounds = sorted({b for p in self.peers for b in (p[2], p[3])})
        worst, where = None, None
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            n = sum(1 for p in self.peers if p[2] <= lo and hi <= p[3])
            if worst is None or n < worst:
                worst, where = n, (lo, hi)
        if worst is not None and worst < self.replication_factor:
            warnings.warn(
                f"range [{where[0]}, {where[1]}) has {worst} owner(s) but "
                f"replication_factor={self.replication_factor} — a single "
                "host loss can stall fetches for under-replicated ranges"
            )

    def _allgather_peers(self, advertise_host: str | None):
        from jax.experimental import multihost_utils

        host = advertise_host or socket.gethostbyname(socket.gethostname())
        mine = np.array(
            [_ip_to_int(host), self.server.port, self.start, self.stop], np.int64
        )
        allv = np.asarray(multihost_utils.process_allgather(mine))
        return [
            (_int_to_ip(int(r[0])), int(r[1]), int(r[2]), int(r[3])) for r in allv
        ]

    # -- Sequence API --------------------------------------------------------
    def __len__(self) -> int:
        return self.total

    @property
    def attrs(self) -> dict:
        return self.ds.attrs

    def _is_self(self, rank: int) -> bool:
        _, port, s0, s1 = self.peers[rank]
        return (
            s0 == self.start
            and s1 == self.stop
            and port in (0, self.server.port)
        )

    def _owners(self, i: int) -> tuple[int, ...]:
        """Every REMOTE peer rank whose advertised span contains global
        index ``i`` (self-entries excluded — local reads never touch the
        network). With replication this is the replica set a fetch may
        fail over across."""
        ranks = tuple(
            rank
            for rank, (_, _, s0, s1) in enumerate(self.peers)
            if s0 <= i < s1 and not self._is_self(rank)
        )
        if not ranks and not (self.start <= i < self.stop):
            raise IndexError(i)
        return ranks

    # -- peer health / quarantine -------------------------------------------
    def _quarantined(self, rank: int) -> bool:
        return self._health_table.quarantined(rank)

    def _bump_quarantine(self, rank: int) -> bool:
        """Record one more failure for ``rank`` in the health table —
        re-probe deadline pushed out by the current backoff, backoff
        doubled up to the cap (``utils.wire.HealthTable`` — THE single
        implementation of the quarantine clock, shared by the fetch path,
        the prober, and the fleet router). Returns True when this created
        the entry (a fresh peer-down transition)."""
        return self._health_table.bump(rank)

    def _mark_peer_down(self, rank: int, err: BaseException, failover: bool) -> None:
        """Quarantine a peer after a transport failure: evict its pooled
        sockets (they spent the outage half-dead), arm the re-probe backoff,
        and wake the background prober so the peer rejoins automatically
        when it answers pings again."""
        host, port, s0, s1 = self.peers[rank]
        announce = self._bump_quarantine(rank)
        self._pool.evict(rank)
        if announce:
            with self._lock:
                self.quarantine_events += 1
            from .. import telemetry as tel

            tel.counter("store_quarantine_events_total").inc()
            tel.emit(
                "failover", peer=rank, host=host, port=port,
                error=type(err).__name__,
                has_replica=bool(failover),
            )
            warnings.warn(
                f"shard peer {host}:{port} (range [{s0}, {s1})) is down "
                f"({type(err).__name__}: {err}): quarantined"
                + (", failing over to a replica" if failover else
                   " — range has NO live replica; fetches keep attempting it")
            )
        self._ensure_prober()

    def _mark_peer_up(self, rank: int, announce: bool = False) -> None:
        was = self._health_table.lift(rank)
        if was is not None and announce:
            host, port, s0, s1 = self.peers[rank]
            warnings.warn(
                f"shard peer {host}:{port} (range [{s0}, {s1})) answers "
                f"again after {was['failures']} failed probe(s): quarantine "
                "lifted"
            )

    def stats(self) -> dict:
        """The data plane's counters in the same shape the serve surfaces
        use (and published through the same telemetry registry): remote /
        failover fetch totals, peer-down events, cache occupancy, and the
        current quarantine census — the operator's one-call health view of
        the elastic store."""
        with self._lock:
            out = {
                "remote_fetches": self.remote_fetches,
                "failover_fetches": self.failover_fetches,
                "quarantine_events": self.quarantine_events,
                "cache_entries": len(self._cache),
                "cache_size": self._cache_size,
            }
        with self._health_lock:
            out["quarantined_peers"] = len(self._health)
        out["peers"] = len(self.peers)
        from .. import telemetry as tel

        tel.publish("sharded_store", out)
        return out

    def _replica_order(self, ranks) -> list[int]:
        """Failover order over a replica set: healthy peers first, rotated
        by a per-client constant so different clients spread load across
        replicas instead of all hammering the first-listed owner;
        quarantined peers last (soonest-re-probe first) as a final resort
        when nothing healthy is left (``utils.wire.HealthTable.order``)."""
        return self._health_table.order(ranks, rot=self._rot)

    def _ensure_prober(self) -> None:
        with self._health_lock:
            if self._probe_thread is not None and self._probe_thread.is_alive():
                return
            self._probe_thread = threading.Thread(
                target=self._probe_loop, name="hydragnn-shard-prober",
                daemon=True,
            )
            self._probe_thread.start()

    def _probe_loop(self) -> None:
        """Background re-probe of quarantined peers (one lazy daemon
        thread, alive only while something is quarantined): ping — a
        protocol op the server answers without touching its dataset — and
        lift the quarantine when the peer responds with the range it was
        advertised for. A wrong-range pong stays quarantined: resurrecting
        a restarted-with-different-data peer would silently serve wrong
        samples."""
        while not self._probe_stop.wait(self.probe_interval):
            with self._health_lock:
                if not self._health:
                    # all clear: exit. Clearing the handle UNDER the lock
                    # closes the race with _ensure_prober — a quarantine
                    # recorded while this thread is still is_alive() but
                    # past its exit decision must start a fresh prober,
                    # not trust a dying one
                    self._probe_thread = None
                    return
                now = time.monotonic()
                due = [r for r, h in self._health.items() if now >= h["until"]]
            for rank in due:
                host, port, s0, s1 = self.peers[rank]
                try:
                    # watchdog-bracketed like any replica round-trip: a
                    # quarantined peer reborn as a byte-dribbler would
                    # otherwise wedge THE prober thread forever (it is a
                    # singleton — a hung probe means no quarantine is ever
                    # probe-lifted again for the rest of the process)
                    cell: dict = {"sock": None}
                    with self._guard_round_trip(host, port, cell):
                        z = _unpack_arrays(self._request(
                            rank, host, port, attempts=1, _sock_cell=cell,
                            ping=np.asarray(1, np.int64),
                        ))
                    # the shared pong validation (wire.check_pong): the
                    # peer must advertise the exact range it is listed for
                    check_pong(
                        z, f"probe of shard peer {host}:{port}",
                        have=[s0, s1],
                    )
                except (ConnectionError, OSError):
                    self._bump_quarantine(rank)
                    continue
                self._mark_peer_up(rank, announce=True)

    def _request(
        self, rank: int, host: str, port: int, attempts: int | None = None,
        _sock_cell: dict | None = None, **fields,
    ) -> bytes:
        """One request/response round-trip on a pooled socket — no shared
        lock held, so concurrent callers overlap their network waits. The
        socket returns to the pool only after a clean round-trip; any error
        closes it (a half-read stream cannot be reused).

        Transient-fault policy (the request is idempotent, so retrying is
        always safe): a stale POOLED socket (dropped by the peer/NAT while
        parked) retries immediately on a fresh connection without counting
        an attempt; a FRESH-connection failure — connect refused/reset/
        timed out mid-stream — retries per the shared ``utils.retry``
        policy (``HYDRAGNN_STORE_RETRIES`` total attempts, exponential
        backoff + jitter, a warning per retry), so a blip in the fabric
        degrades to a logged pause instead of killing the epoch. The last
        failure re-raises. ``attempts=1`` pins a single try — the failover
        path does its own retrying ACROSS replicas, where a per-replica
        backoff loop would multiply the outage by the replica count.
        ``_sock_cell`` (when given) exposes the in-flight socket so a
        watchdog can sever a wedged round-trip from its monitor thread.
        The round-trip itself is ``utils.wire.RoundTripper.request`` —
        this wrapper only resolves the retry policy (store flag vs pinned
        attempts)."""
        from ..utils.retry import RetryPolicy, store_policy

        policy = (
            store_policy() if attempts is None
            else RetryPolicy(attempts=max(1, int(attempts)))
        )
        return self._rt.request(
            rank, host, port, policy=policy, _sock_cell=_sock_cell, **fields
        )

    def _failover_request(self, owner_ranks, fields_for, what: str):
        """One replicated request: walk the replica set in
        ``_replica_order``, one attempt per replica per round — a transport
        failure quarantines the peer and moves on; only when EVERY replica
        failed does a round end, sleeping per the shared retry policy
        before the next sweep (the fabric may be blipping, not the hosts).
        Protocol errors (``_check_status``) raise immediately on purpose.

        A watchdog deadline brackets each attempt: a peer that dribbles
        bytes forever (resetting the per-recv socket timeout every chunk)
        gets its socket severed from the monitor thread, which surfaces
        here as an OSError and takes the normal quarantine+failover path.

        When trace propagation is armed, the walk runs under one
        ``request_id`` (adopted from the ambient context or minted here),
        every hop emits a ``store_hop`` child record naming the peer it
        tried (``outcome=quarantined`` for the transport-failed peer,
        ``outcome=served`` for the winner), and the peer sees the same id
        in its own journal — one fetch, one cross-process timeline.

        Returns ``(decoded frame, rank, s0, s1)`` of the replica that
        answered. ``fields_for(s0, s1)`` builds the request for an owner
        advertising ``[s0, s1)`` — replicas of one range may be advertised
        with different spans, and local indices are span-relative."""
        from .. import telemetry as tel

        traced = tel.propagate_enabled()
        if not traced:
            return self._failover_walk(owner_ranks, fields_for, what, False)
        rid = tel.get_context().get("request_id") or tel.new_request_id()
        with tel.scoped_context(request_id=rid):
            return self._failover_walk(owner_ranks, fields_for, what, True)

    def _failover_walk(self, owner_ranks, fields_for, what: str,
                       traced: bool):
        from .. import telemetry as tel
        from ..utils.retry import store_policy

        policy = store_policy()
        last_err: BaseException | None = None
        failed_over = False
        hop = 0
        for rnd in range(policy.attempts):
            if rnd:
                sleep_s = policy.delay(rnd)
                warnings.warn(
                    f"{what}: every replica failed "
                    f"({type(last_err).__name__}: {last_err}); retry round "
                    f"{rnd}/{policy.attempts - 1} in {sleep_s:.2f}s "
                    "(HYDRAGNN_STORE_RETRIES tunes the cap)"
                )
                time.sleep(sleep_s)
            order = self._replica_order(owner_ranks)
            for rank in order:
                host, port, s0, s1 = self.peers[rank]
                cell: dict = {"sock": None}
                t0_wall = time.time()
                try:
                    with self._guard_round_trip(host, port, cell):
                        z = _unpack_arrays(self._request(
                            rank, host, port, attempts=1, _sock_cell=cell,
                            **fields_for(s0, s1),
                        ))
                except (ConnectionError, OSError) as e:
                    last_err = e
                    failed_over = True
                    if traced:
                        tel.emit(
                            "store_hop", hop=hop, peer=rank, host=host,
                            port=port, outcome="quarantined",
                            error=type(e).__name__,
                        )
                        if tel.trace_enabled():
                            tel.add_span(
                                f"store_hop:{rank}", t0_wall,
                                time.time() - t0_wall,
                                args={"peer": rank, "outcome": "quarantined"},
                            )
                    hop += 1
                    self._mark_peer_down(rank, e, failover=len(order) > 1)
                    continue
                self._check_status(z, host, port, s0, s1)
                self._mark_peer_up(rank)
                if traced:
                    tel.emit(
                        "store_hop", hop=hop, peer=rank, host=host,
                        port=port, outcome="served",
                        failed_over=bool(failed_over),
                        dur_s=round(time.time() - t0_wall, 6),
                    )
                    if tel.trace_enabled():
                        tel.add_span(
                            f"store_hop:{rank}", t0_wall,
                            time.time() - t0_wall,
                            args={"peer": rank, "outcome": "served"},
                        )
                if failed_over:
                    n = int(z.get("n", np.asarray(0)))
                    with self._lock:
                        self.failover_fetches += max(n, 0)
                    tel.counter("store_failover_fetches_total").inc(max(n, 0))
                return z, rank, s0, s1
        raise ConnectionError(
            f"{what}: all {len(owner_ranks)} replica(s) failed after "
            f"{policy.attempts} round(s); last error: "
            f"{type(last_err).__name__}: {last_err}"
        )

    def _guard_round_trip(self, host: str, port: int, cell: dict):
        """Watchdog context for one replica round-trip
        (``utils.wire.RoundTripper.guard``): if the round-trip outlives
        ~1.25x the peer timeout (the per-recv socket timeout never fired —
        a dribbling peer), the monitor thread severs the in-flight socket,
        converting the hang into the OSError the failover path already
        handles. Disabled for non-finite/zero timeouts."""
        return self._rt.guard(
            host, port, cell, what=f"shard round-trip to {host}:{port}"
        )

    @staticmethod
    def _check_status(z: dict[str, np.ndarray], host: str, port: int,
                      s0: int, s1: int):
        n = int(z["n"])
        if n == -3:
            detail = bytes(np.asarray(z.get("detail", []), np.uint8)).decode(
                errors="replace"
            )
            raise RuntimeError(
                f"shard server at {host}:{port} failed serving the request: "
                f"{detail or 'unknown error'}"
            )
        if n == -2:
            raise RuntimeError(
                f"shard fetch rejected by {host}:{port}: auth token "
                "mismatch (pass the same auth_token on every host)"
            )
        if n == -1:
            have = z.get("have", "?")
            raise RuntimeError(
                f"shard fetch misrouted: peer at {host}:{port} "
                f"owns global range {have}, expected [{s0}, {s1})"
                " — check the advertised addresses (loopback "
                "hostnames on multi-host clusters are the usual "
                "cause; pass advertise_host explicitly)"
            )

    def __getitem__(self, i) -> GraphSample:
        i = int(i)
        if self.start <= i < self.stop:
            return self.ds[i - self.start]
        return self.fetch([i])[0]

    def sample_sizes(self, indices) -> np.ndarray:
        """[k, 2] (num_nodes, num_edges) for arbitrary GLOBAL indices. The
        full size table is exchanged ONCE (one request per peer, a few
        int64s per sample), so bucket planning never turns into per-sample
        content fetches across the network."""
        if self._sizes is None:
            with self._sizes_lock:
                if self._sizes is None:
                    self._sizes = self._fetch_all_sizes()
        return self._sizes[np.asarray(indices, np.int64)]

    def _fetch_all_sizes(self) -> np.ndarray:
        out = np.zeros((self.total, 2), np.int64)
        covered = np.zeros(self.total, bool)
        out[self.start:self.stop] = self.ds.sample_sizes(
            range(self.stop - self.start)
        )
        covered[self.start:self.stop] = True
        by_span: dict[tuple[int, int], list[int]] = {}
        for rank, (_, _, s0, s1) in enumerate(self.peers):
            if not self._is_self(rank):
                by_span.setdefault((s0, s1), []).append(rank)
        errors: list[str] = []
        for (s0, s1), ranks in sorted(by_span.items()):
            if covered[s0:s1].all():
                continue  # mirror of a span already served (e.g. our own)
            try:
                z, _, a0, a1 = self._failover_request(
                    ranks,
                    lambda a0, a1: dict(
                        idx=np.zeros((0,), np.int64),
                        range=np.asarray([a0, a1], np.int64),
                        sizes=np.asarray(1, np.int64),
                    ),
                    what=f"size table for range [{s0}, {s1})",
                )
            except (ConnectionError, OSError) as e:
                # a dead span GROUP is not yet fatal: replicas advertised
                # under different span boundaries may still cover this
                # data (a later, finer span fills it in) — only genuinely
                # uncovered indices after the sweep are an error
                errors.append(f"[{s0}, {s1}): {e}")
                continue
            out[a0:a1] = z["sizes"]
            covered[a0:a1] = True
        if not covered.all():
            lo = int(np.argmin(covered))
            raise ConnectionError(
                f"size table incomplete: no live owner covers index {lo} "
                f"(failed spans: {'; '.join(errors) or 'none'})"
            )
        return out

    def fetch(self, indices) -> list[GraphSample]:
        """Batched read of arbitrary GLOBAL indices: local ones from mmap,
        remote ones with ONE request per owning host. Only the cache
        bookkeeping is serialized; the network round-trips run on pooled
        per-call sockets, so concurrent callers (PrefetchLoader workers)
        overlap their remote fetches.

        Mutability contract: LOCAL samples are zero-copy READ-ONLY mmap
        views (an in-place write raises — loud, safe, and free); REMOTE
        samples are independent writable copies (the LRU cache keeps its
        own pristine instance, so a caller mutating one can never corrupt
        a later cache hit). Transforms that write in place must copy
        first; transforms that build new arrays work on both."""
        out: dict[int, GraphSample] = {}
        by_owner: dict[tuple[int, ...], list[int]] = {}
        remote: list[int] = []
        for i in map(int, indices):
            if self.start <= i < self.stop:
                out[i] = self.ds[i - self.start]  # zero-copy mmap read
            else:
                remote.append(i)
        if remote:
            pending: set[int] = set()
            hits: dict[int, GraphSample] = {}
            with self._lock:
                for i in remote:
                    if i in self._cache:
                        self._cache.move_to_end(i)
                        hits[i] = self._cache[i]  # reference only under lock
                    elif i not in pending:
                        pending.add(i)
                        # grouped by REPLICA SET, not single owner: every
                        # index in a group can fail over across the same
                        # peers, so one dead host re-routes the whole
                        # request instead of killing the batch
                        by_owner.setdefault(self._owners(i), []).append(i)
            # copy on hit OUTSIDE the lock (the lock serializes bookkeeping
            # only — array memcpy under it would stall concurrent workers):
            # callers mutate samples in place (transforms); the cache's
            # instance stays pristine
            for i, s in hits.items():
                out[i] = _copy_sample(s)
        def fetch_owner(item):
            ranks, idxs = item
            z, _, _, _ = self._failover_request(
                ranks,
                lambda a0, a1: dict(
                    idx=np.asarray([i - a0 for i in idxs], np.int64),
                    range=np.asarray([a0, a1], np.int64),
                ),
                what=f"fetch of {len(idxs)} sample(s) from range "
                     f"[{min(idxs)}, {max(idxs)}]",
            )
            return idxs, _samples_from_frame(z)

        if len(by_owner) <= 1:
            results = [fetch_owner(it) for it in by_owner.items()]
        else:
            # a shuffled global batch touches many owners — issue those
            # round-trips concurrently instead of paying one RTT per owner.
            # The executor is persistent (created once, closed with the
            # store): per-batch spawn/teardown would burn host CPU in the
            # hot path it exists to hide.
            if self._executor is None:
                with self._lock:
                    if self._executor is None:
                        # sized for CONCURRENT callers, not one fetch: N
                        # prefetch workers each fanning out to several
                        # owners share this pool, so a peers-count cap
                        # would serialize them against each other
                        self._executor = ThreadPoolExecutor(16)
            results = list(self._executor.map(fetch_owner, by_owner.items()))
        for idxs, samples in results:
            # the caller gets the freshly decoded instance; the cache keeps
            # its OWN copy (made before taking the lock) so later hits are
            # unaffected by whatever the caller does to this one
            cache_copies = [_copy_sample(s) for s in samples]
            from .. import telemetry as tel

            tel.counter("store_remote_fetches_total").inc(len(samples))
            with self._lock:
                self.remote_fetches += len(samples)
                for i, s, c in zip(idxs, samples, cache_copies):
                    out[i] = s
                    self._cache[i] = c
                while len(self._cache) > self._cache_size:
                    self._cache.popitem(last=False)
        # duplicate REMOTE indices must not share one writable instance
        # across result positions (the isolation contract above); local
        # read-only mmap views are safe to share
        result: list[GraphSample] = []
        emitted: set[int] = set()
        for i in map(int, indices):
            s = out[i]
            if i in emitted and not (self.start <= i < self.stop):
                s = _copy_sample(s)
            else:
                emitted.add(i)
            result.append(s)
        return result

    def fetch_many(self, indices) -> list[GraphSample]:
        """Bulk streaming read: the screening planner's wire op
        (``hydragnn_tpu.screen``). Same replica-set grouping and failover as
        :meth:`fetch` — ONE framed request per span per replica set, local
        spans straight from mmap — but it BYPASSES the LRU cache entirely:

        * no cache-bookkeeping lock traffic and no pristine-copy memcpy per
          sample on the hot path (a screen touches each sample exactly once,
          so a hit can never pay back the copy), and
        * no pollution — a multi-million-graph sweep would otherwise evict
          the training/serving working set the cache exists for.

        The per-sample :meth:`fetch` surface (cache, copy-on-hit isolation,
        duplicate-instance contract) is untouched; ``fetch`` remains the
        right call for loaders that revisit samples. Remote samples are
        freshly decoded (writable) instances; LOCAL spans remain zero-copy
        READ-ONLY mmap views, as in ``fetch``. Duplicate remote indices get
        independent copies (same isolation contract as ``fetch``)."""
        out: dict[int, GraphSample] = {}
        by_owner: dict[tuple[int, ...], list[int]] = {}
        for i in map(int, indices):
            if self.start <= i < self.stop:
                out[i] = self.ds[i - self.start]  # zero-copy mmap read
            elif i not in out:
                out[i] = None  # type: ignore[assignment]  # placeholder: dedup
                by_owner.setdefault(self._owners(i), []).append(i)

        def fetch_owner(item):
            ranks, idxs = item
            z, _, _, _ = self._failover_request(
                ranks,
                lambda a0, a1: dict(
                    idx=np.asarray([i - a0 for i in idxs], np.int64),
                    range=np.asarray([a0, a1], np.int64),
                ),
                what=f"bulk fetch of {len(idxs)} sample(s) from range "
                     f"[{min(idxs)}, {max(idxs)}]",
            )
            return idxs, _samples_from_frame(z)

        if len(by_owner) <= 1:
            results = [fetch_owner(it) for it in by_owner.items()]
        else:
            # same persistent fan-out pool as fetch: many owners, one RTT
            if self._executor is None:
                with self._lock:
                    if self._executor is None:
                        self._executor = ThreadPoolExecutor(16)
            results = list(self._executor.map(fetch_owner, by_owner.items()))
        n_remote = 0
        for idxs, samples in results:
            n_remote += len(samples)
            for i, s in zip(idxs, samples):
                out[i] = s
        if n_remote:
            from .. import telemetry as tel

            tel.counter("store_remote_fetches_total").inc(n_remote)
            with self._lock:
                self.remote_fetches += n_remote
        result: list[GraphSample] = []
        emitted: set[int] = set()
        for i in map(int, indices):
            s = out[i]
            if i in emitted and not (self.start <= i < self.stop):
                s = _copy_sample(s)
            else:
                emitted.add(i)
            result.append(s)
        return result

    def pad_spec(self, batch_size: int, node_multiple: int = 8, edge_multiple: int = 128):
        """PadSpec from shard-local writer stats, maxed across hosts when
        under jax.distributed (stats are per-shard)."""
        a = dict(self.attrs)
        if "max_nodes" not in a:
            raise ValueError("packed shard lacks size stats; re-write with PackedWriter")
        try:
            import jax

            multi = jax.process_count() > 1
        except Exception:
            multi = False
        if multi:
            # MUST succeed: silently falling back to shard-local maxima
            # would give hosts different static shapes and hang/crash the
            # SPMD program far from the root cause
            from jax.experimental import multihost_utils

            stats = np.asarray(
                multihost_utils.process_allgather(
                    np.array([a["max_nodes"], a["max_edges"]], np.int64)
                )
            )
            a["max_nodes"] = int(stats[:, 0].max())
            a["max_edges"] = int(stats[:, 1].max())
        from .packed import pad_spec_from_stats

        return pad_spec_from_stats(a, batch_size, node_multiple, edge_multiple)

    def loader(
        self,
        batch_size: int,
        rank: int = 0,
        world: int = 1,
        seed: int = 0,
        shuffle: bool = True,
        pad=None,
        **kw,
    ):
        from ..graphs.batching import GraphLoader

        return GraphLoader(
            self,
            batch_size,
            pad=pad or self.pad_spec(batch_size),
            shuffle=shuffle,
            seed=seed,
            rank=rank,
            world=world,
            **kw,
        )

    def close(self) -> None:
        self._probe_stop.set()
        self.server.close()
        if self._executor is not None:
            self._executor.shutdown(wait=False)
        self._pool.close()


def _ip_to_int(ip: str) -> int:
    return int.from_bytes(socket.inet_aton(ip), "big")


def _int_to_ip(v: int) -> str:
    return socket.inet_ntoa(v.to_bytes(4, "big"))


__all__ = [
    "ShardServer",
    "ShardedStore",
    "StoreConfig",
    "live_servers",
    "store_config_defaults",
]
