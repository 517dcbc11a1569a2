"""Buffered-async round engine: quorum commits, staleness-weighted
mask folds, live transport faults, crash-consistent resume.

The synchronous engine (`repro.api.protocol.run_round`) is a barrier:
a round waits for every cohort's uplink before aggregating.  At 1000+
clients the barrier is the tail-latency product of the whole fleet, so
this module replaces it with a FedBuff-style buffer:

  * every tick the server LAUNCHES the current cohort (same downlink
    wire, same vmapped `client_update`, same per-round key schedule as
    `run_round` — bit-identical client phase);
  * each client's payload is ENCODED to a real `WireMessage` (packed
    uint32 mask words + float sidecar + CRC32 header) and handed to the
    transport, where `runtime.fault.FaultInjector` may crash it, drop
    its pod, delay it whole rounds, or flip bits in transit;
  * arrivals FOLD into the round buffer as they land: the checksum is
    verified first (corrupt uplinks are rejected and retransmitted with
    bounded backoff, then cut), the decoded words join the buffer and
    a running popcount accumulator (`aggregation.fold_popcount`) tracks
    the live ones-count without re-touching buffered words;
  * the round COMMITS when the buffer reaches quorum (or a deadline
    forces it): fold weights are `aggregation.staleness_weights` —
    |D_i| discounted by ``(1+s)^-alpha`` and renormalized over the
    buffer — and the reduction goes through `payloads.stack_payloads`
    into the algorithm's own `aggregate`, i.e. the SAME
    `batched_packed_mean` / `mean_from_words` kernel as the barrier
    path.  With zero faults and ``quorum_frac=1`` every commit is
    bit-identical to `run_round` (tests/test_async_engine.py gates
    this, wire bits included).

Crash consistency: `save()` writes the full engine — server state,
buffered payloads, in-flight messages, tick/version counters, comm
totals — through `ckpt.save_bundle` (tmp + os.replace, manifest last).
Fault draws are pure functions of (seed, round, client, attempt)
(`runtime.fault`), so a restored engine REPLAYS the identical fault
sequence; there is no RNG state to lose, only the tick cursor, which
the bundle carries.

Accounting: `uplink_bits_measured` counts every delivered attempt's
``wire_bits + sidecar_bits`` (rejected attempts consumed the wire too);
the CRC32 header is metered separately as ``uplink_header_bits`` so the
mask Bpp metric, the CommLedger feed, and `analysis.comm_model`'s
static tables keep meaning exactly what the codec put on the mask
stream.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import codecs as codecs_lib
from repro.api import payloads as plds
from repro.api import protocol
from repro.core import aggregation
from repro.ckpt import checkpoint as ckptlib
from repro.runtime.fault import FaultInjector

Pytree = Any

_NONE = lambda x: x is None


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Commit policy for the buffered-async engine.

    quorum_frac:     commit once ceil(quorum_frac * n_clients) uplinks
                     are buffered (1.0 = the synchronous barrier).
    deadline_rounds: force-commit a non-empty buffer after this many
                     ticks without a commit (no quorum starvation).
    max_staleness:   arrivals trained against a theta more than this
                     many commits old are discarded, not folded.
    staleness_alpha: discount exponent of ``(1 + s)^-alpha``.
    """
    quorum_frac: float = 1.0
    deadline_rounds: int = 4
    max_staleness: int = 4
    staleness_alpha: float = 0.5

    @property
    def alpha(self) -> float:
        return self.staleness_alpha

    def quorum_count(self, n_clients: int) -> int:
        k = int(np.ceil(self.quorum_frac * n_clients))
        return min(max(k, 1), n_clients)


@dataclasses.dataclass
class _InFlight:
    """One uplink on the wire (client -> server, not yet accepted)."""
    client: int
    version: int          # server commit count the client trained from
    round: int            # tick the client was launched at
    deliver: int          # tick the current attempt lands
    attempt: int          # 0 = first transmission
    size: float           # |D_i|
    msg: codecs_lib.WireMessage
    metrics: Dict[str, float]


@dataclasses.dataclass
class _Buffered:
    """One verified arrival waiting in the round buffer."""
    client: int
    version: int
    round: int
    size: float
    payload: Any
    metrics: Dict[str, float]


class AsyncRoundEngine:
    """Host-sim buffered-async server around one `FedAlgorithm`.

    Drive it one tick at a time::

        eng = AsyncRoundEngine(algo, state, data_like, sizes, key,
                               config=AsyncConfig(quorum_frac=0.8),
                               injector=FaultInjector(K, crash_prob=.3))
        for t in range(T):
            commits = eng.tick(data_t)      # 0 or 1 commits per tick
        eng.flush()                         # fold any tail arrivals

    ``data_like`` is one TICK's client batch pytree (leading axes
    [K, H, ...]) — shapes only; it seeds the payload/wire templates the
    checkpoint restore path rebuilds messages with.
    """

    def __init__(self, algo, state, data_like, sizes, key,
                 config: Optional[AsyncConfig] = None,
                 injector: Optional[FaultInjector] = None,
                 codec=None):
        self.algo = algo
        self.state = state
        self.config = config or AsyncConfig()
        self.injector = injector
        self.codec = (algo.codec if codec is None
                      else codecs_lib.get_codec(codec)
                      if isinstance(codec, str) else codec)
        self.sizes = np.asarray(jax.device_get(sizes), np.float32)
        self.n_clients = int(self.sizes.shape[0])
        self.key = key

        self.tick_idx = 0
        self.version = 0            # commits so far = theta generation
        self.last_commit_tick = 0
        self.buffer: List[_Buffered] = []
        self.pending: List[_InFlight] = []
        self.events: List[dict] = []
        self._event_seq = 0         # monotone event ordering cursor
        self.buffer_ones = 0        # running popcount over the buffer
        self.totals = {"uplink_bits_measured": 0.0,
                       "uplink_header_bits": 0.0,
                       "downlink_bits": 0.0, "commits": 0}
        self._since_commit = {"uplink_bits_measured": 0.0,
                              "uplink_header_bits": 0.0,
                              "downlink_bits": 0.0}
        self._last_downlink_bpp = 0.0

        # -- traced phases (split at an INTEGER boundary: the packed
        # uint32 words cross between them, so the jit split cannot
        # perturb float results vs run_round's single jit) ------------
        def client_phase(state_, data, key_):
            dl, client_state = protocol.client_view(self.algo, state_,
                                                    key_)
            keys = jax.random.split(key_, self.n_clients)
            payloads, metrics = jax.vmap(
                self.algo.client_update,
                in_axes=(None, 0, 0))(client_state, data, keys)
            return dl, payloads, metrics

        self._client_phase = jax.jit(client_phase)

        def agg_phase(state_, batched, sizes_, staleness, part, metrics):
            wn = aggregation.staleness_weights(
                sizes_, staleness, self.config.staleness_alpha)
            new_state = self.algo.aggregate(state_, batched, wn, part)
            bpps = jax.vmap(lambda p: p.bpp())(batched)
            # the weighted client metrics reduce inside this jit, as
            # run_round reduces them inside its own: an eager sum
            # rounds differently
            return (new_state, jnp.sum(bpps * wn),
                    {k: jnp.sum(v * wn) for k, v in metrics.items()})

        self._agg_phase = jax.jit(agg_phase)

        # -- payload / wire templates (shapes are static per algo):
        # the restore path unflattens bundle arrays with this treedef
        # and rebuilds WireMessages with this meta -------------------
        pshape = jax.eval_shape(
            lambda s, d, k: self.algo.client_update(s, d, k)[0],
            state, jax.tree_util.tree_map(lambda x: x[0], data_like),
            key)
        template = jax.tree_util.tree_map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype), pshape)
        tleaves, tdef = jax.tree_util.tree_flatten(template,
                                                   is_leaf=_NONE)
        self._payload_template = template
        self._payload_treedef = tdef
        self._payload_none = tuple(l is None for l in tleaves)
        tmsg = self.codec.encode(template)
        self._wire_meta = tmsg.meta
        self._payload_cls = tmsg.payload_cls
        self._degraded_restore = False

    # -- policy shorthands ------------------------------------------------

    @property
    def quorum(self) -> int:
        return self.config.quorum_count(self.n_clients)

    def _event(self, kind: str, **kw):
        """Append an event record.  Every record carries a monotone
        ``seq`` (total order over the engine's whole life, survives
        save/restore) so a crash-restart consumer can assert
        exactly-once semantics instead of matching on event counts;
        per-delivery events additionally carry the transmission
        ``attempt`` for (round, client) idempotency keys."""
        self.events.append(dict(kind=kind, seq=self._event_seq,
                                tick=self.tick_idx, **kw))
        self._event_seq += 1

    # -- tick: launch -> deliver -> maybe commit --------------------------

    def tick(self, data, key=None) -> List[dict]:
        """One engine tick.  Returns the (possibly empty) list of
        commit metric dicts produced this tick."""
        t = self.tick_idx
        self._launch(data, t, key)
        self._deliver(t)
        out = self._maybe_commit(t)
        self.tick_idx = t + 1
        return out

    def flush(self) -> List[dict]:
        """Drain the wire (advancing ticks, no new launches) and
        force-commit whatever ends up buffered — end-of-training tail
        collection.  Bounded: retries are capped, so pending empties."""
        out: List[dict] = []
        for _ in range(100_000):
            t = self.tick_idx
            self._deliver(t)
            if not self.pending:
                out.extend(self._maybe_commit(t, force=True))
                return out
            out.extend(self._maybe_commit(t))
            self.tick_idx = t + 1
        raise RuntimeError("flush did not drain the pending queue")

    def _launch(self, data, t: int, key=None):
        if key is None:
            key = jax.random.fold_in(self.key, t)
        dl, payloads, metrics = self._client_phase(self.state, data,
                                                   key)
        if dl is not None:
            self._last_downlink_bpp = float(dl.bpp())
            dbits = float(dl.wire_bits() + dl.sidecar_bits()
                          ) * self.n_clients
            self._since_commit["downlink_bits"] += dbits
            self.totals["downlink_bits"] += dbits
        inj = self.injector
        dropped = (inj.dropped(t) if inj is not None
                   else np.zeros(self.n_clients, bool))
        delays = (inj.delay_rounds(t) if inj is not None
                  else np.zeros(self.n_clients, np.int64))
        host_metrics = {k: np.asarray(jax.device_get(v))
                        for k, v in metrics.items()}
        for c in range(self.n_clients):
            if dropped[c]:
                self._event("drop", client=c, round=t)
                continue
            msg = self.codec.encode(plds.slice_payload(payloads, c))
            if int(delays[c]) > 0:
                self._event("straggle", client=c, round=t,
                            late=int(delays[c]))
            self.pending.append(_InFlight(
                client=c, version=self.version, round=t,
                deliver=t + int(delays[c]), attempt=0,
                size=float(self.sizes[c]), msg=msg,
                metrics={k: float(v[c]) if getattr(v, "ndim", 0)
                         else float(v)
                         for k, v in host_metrics.items()}))

    def _deliver(self, t: int):
        inj = self.injector
        still: List[_InFlight] = []
        for e in self.pending:
            if e.deliver > t:
                still.append(e)
                continue
            msg = e.msg
            if inj is not None and inj.corrupt_attempt(
                    e.round, e.client, e.attempt):
                msg = dataclasses.replace(
                    e.msg, words=inj.corrupt_words(
                        e.msg.words, e.round, e.client, e.attempt))
            # the delivery consumed the wire whether or not it verifies
            abits = float(msg.wire_bits + msg.sidecar_bits)
            self._since_commit["uplink_bits_measured"] += abits
            self.totals["uplink_bits_measured"] += abits
            self._since_commit["uplink_header_bits"] += msg.header_bits
            self.totals["uplink_header_bits"] += msg.header_bits
            if not msg.verify():
                if e.attempt >= (inj.max_retries if inj else 0):
                    self._event("cut", client=e.client, round=e.round,
                                attempts=e.attempt + 1)
                    continue
                backoff = max(1, int(np.ceil(
                    inj.backoff_rounds * (e.attempt + 1))))
                self._event("corrupt_reject", client=e.client,
                            round=e.round, attempt=e.attempt,
                            retry_at=t + backoff)
                still.append(dataclasses.replace(
                    e, attempt=e.attempt + 1, deliver=t + backoff))
                continue
            staleness = self.version - e.version
            if staleness > self.config.max_staleness:
                self._event("stale_drop", client=e.client,
                            round=e.round, staleness=staleness,
                            attempt=e.attempt)
                continue
            payload = self.codec.decode(msg)
            acc = self.buffer_ones
            for w in jax.tree_util.tree_leaves(
                    getattr(payload, "words", ()), is_leaf=_NONE):
                if w is not None:
                    acc = aggregation.fold_popcount(acc, w)
            ones = acc - self.buffer_ones
            self.buffer_ones = acc
            self.buffer.append(_Buffered(
                client=e.client, version=e.version, round=e.round,
                size=e.size, payload=payload, metrics=e.metrics))
            self._event("fold", client=e.client, round=e.round,
                        staleness=staleness, ones=ones,
                        attempt=e.attempt)
        self.pending = still

    def _maybe_commit(self, t: int, force: bool = False) -> List[dict]:
        # prune anything the buffer outlived
        fresh: List[_Buffered] = []
        for e in self.buffer:
            if self.version - e.version <= self.config.max_staleness:
                fresh.append(e)
            else:
                self._event("stale_drop", client=e.client,
                            round=e.round,
                            staleness=self.version - e.version)
        self.buffer = fresh
        if not self.buffer:
            return []
        deadline = (t - self.last_commit_tick
                    >= self.config.deadline_rounds)
        if len(self.buffer) < self.quorum and not (force or deadline):
            return []
        return [self._commit(t, forced=force or deadline)]

    def _commit(self, t: int, forced: bool = False) -> dict:
        entries, self.buffer = self.buffer, []
        self.buffer_ones = 0
        B = len(entries)
        batched = plds.stack_payloads([e.payload for e in entries])
        sizes = jnp.asarray([e.size for e in entries], jnp.float32)
        stal = jnp.asarray([self.version - e.version for e in entries],
                           jnp.float32)
        part = jnp.ones((B,), bool)
        metrics = {k: jnp.asarray([e.metrics[k] for e in entries],
                                  jnp.float32)
                   for k in entries[0].metrics}
        self.state, up_bpp, folded = self._agg_phase(
            self.state, batched, sizes, stal, part, metrics)
        stal_max = int(max(self.version - e.version for e in entries))
        self.version += 1
        self.last_commit_tick = t
        self.totals["commits"] += 1
        out = {"uplink_bpp": float(up_bpp),
               "downlink_bpp": self._last_downlink_bpp,
               "n_folded": B,
               "version": self.version,
               "tick": t,
               "forced": bool(forced),
               "staleness_max": stal_max,
               "clients": [e.client for e in entries]}
        out.update({k: self._since_commit[k] for k in self._since_commit})
        out.update({k: float(v) for k, v in folded.items()})
        self._since_commit = {k: 0.0 for k in self._since_commit}
        self._event("commit", version=self.version, folded=B,
                    forced=bool(forced))
        return out

    # -- crash-consistent checkpointing -----------------------------------

    @staticmethod
    def _payload_checksum(payload) -> int:
        """`aggregation.words_checksum` over a buffered payload's raw
        leaf bytes (uint32 words AND float sidecar alike) — the
        integrity tag `restore` re-verifies before trusting a saved
        buffer entry."""
        leaves = []
        for l in jax.tree_util.tree_leaves(payload, is_leaf=_NONE):
            if l is None:
                continue
            b = np.ascontiguousarray(np.asarray(jax.device_get(l)))
            leaves.append(np.frombuffer(b.tobytes(), dtype=np.uint8))
        return aggregation.words_checksum(leaves)

    def save(self, path: str) -> str:
        """Atomically persist the WHOLE engine: server state, buffered
        payloads, in-flight wire messages, counters, comm totals.  A
        coordinator killed right after `save` resumes byte-identically
        (`restore`), and because every fault draw is a counter hash of
        (seed, round, client, attempt), the replayed fault sequence is
        identical too."""
        arrays, extra = self._save_payload()
        return ckptlib.save_bundle(path, arrays, extra)

    def _save_payload(self):
        """(arrays, extra) the bundle persists — subclasses extend."""
        arrays: Dict[str, Any] = {}
        sleaves, _ = jax.tree_util.tree_flatten(self.state,
                                                is_leaf=_NONE)
        for j, l in enumerate(sleaves):
            arrays[f"state/{j}"] = l
        for i, e in enumerate(self.buffer):
            leaves = jax.tree_util.tree_flatten(e.payload,
                                                is_leaf=_NONE)[0]
            for j, l in enumerate(leaves):
                arrays[f"buf{i}/{j}"] = l
        for i, e in enumerate(self.pending):
            for j, w in enumerate(e.msg.words):
                arrays[f"pend{i}/w{j}"] = w
            for j, w in enumerate(e.msg.sidecar):
                arrays[f"pend{i}/s{j}"] = w
        extra = {
            "tick": self.tick_idx, "version": self.version,
            "last_commit_tick": self.last_commit_tick,
            "buffer_ones": self.buffer_ones,
            "totals": self.totals,
            "since_commit": self._since_commit,
            "last_downlink_bpp": self._last_downlink_bpp,
            "events": self.events,
            "event_seq": self._event_seq,
            "buffer": [{"client": e.client, "version": e.version,
                        "round": e.round, "size": e.size,
                        "metrics": e.metrics,
                        "checksum": self._payload_checksum(e.payload)}
                       for e in self.buffer],
            "pending": [{"client": e.client, "version": e.version,
                         "round": e.round, "deliver": e.deliver,
                         "attempt": e.attempt, "size": e.size,
                         "metrics": e.metrics,
                         "checksum": e.msg.checksum,
                         "n_words": len(e.msg.words),
                         "n_side": len(e.msg.sidecar)}
                        for e in self.pending],
        }
        return arrays, extra

    def restore(self, path: str) -> "AsyncRoundEngine":
        """Inverse of `save` onto a freshly constructed engine (same
        algo / sizes / key / config / injector).

        Every buffered payload is re-verified against the checksum
        `save` stored for it (`aggregation.words_checksum` over the raw
        leaf bytes).  On ANY mismatch the engine refuses to resume from
        the silently-corrupt buffer and falls back to the degraded
        theta-only path (`runtime.elastic.restore_theta_only`'s bundle
        twin): server state + counters survive, the buffer and in-flight
        queue are dropped, and the cut clients simply re-enter at their
        next launch — the same elasticity the protocol already has."""
        arrays, extra = ckptlib.load_bundle(path)
        return self._load_payload(arrays, extra)

    def _load_payload(self, arrays, extra) -> "AsyncRoundEngine":
        self._degraded_restore = False
        sdef = jax.tree_util.tree_structure(self.state, is_leaf=_NONE)
        nstate = sdef.num_leaves
        self.state = jax.tree_util.tree_unflatten(
            sdef, [arrays.get(f"state/{j}") for j in range(nstate)])
        self.tick_idx = int(extra["tick"])
        self.version = int(extra["version"])
        self.last_commit_tick = int(extra["last_commit_tick"])
        self.buffer_ones = int(extra["buffer_ones"])
        self.totals = dict(extra["totals"])
        self._since_commit = dict(extra["since_commit"])
        self._last_downlink_bpp = float(extra["last_downlink_bpp"])
        self.events = list(extra["events"])
        self._event_seq = int(extra.get("event_seq", len(self.events)))
        nleaf = len(self._payload_none)
        self.buffer = []
        for i, meta in enumerate(extra["buffer"]):
            leaves = [None if self._payload_none[j]
                      else arrays[f"buf{i}/{j}"] for j in range(nleaf)]
            payload = jax.tree_util.tree_unflatten(
                self._payload_treedef, leaves)
            stored = meta.get("checksum")
            if stored is not None and \
                    self._payload_checksum(payload) != int(stored):
                return self._restore_degraded(meta, i)
            self.buffer.append(_Buffered(
                client=int(meta["client"]),
                version=int(meta["version"]),
                round=int(meta["round"]), size=float(meta["size"]),
                payload=payload, metrics=dict(meta["metrics"])))
        self.pending = []
        for i, meta in enumerate(extra["pending"]):
            words = [np.asarray(arrays[f"pend{i}/w{j}"], np.uint32)
                     for j in range(int(meta["n_words"]))]
            side = [np.asarray(arrays[f"pend{i}/s{j}"], np.uint32)
                    for j in range(int(meta["n_side"]))]
            msg = codecs_lib.WireMessage(
                self.codec.name, self._payload_cls, words, side,
                self._wire_meta, checksum=int(meta["checksum"]))
            self.pending.append(_InFlight(
                client=int(meta["client"]),
                version=int(meta["version"]),
                round=int(meta["round"]),
                deliver=int(meta["deliver"]),
                attempt=int(meta["attempt"]), size=float(meta["size"]),
                msg=msg, metrics=dict(meta["metrics"])))
        return self

    def _restore_degraded(self, meta: dict, slot: int
                          ) -> "AsyncRoundEngine":
        """Checksum-mismatch fallback: keep the restored server state
        and counters (theta is what matters — `elastic` doctrine), but
        refuse the buffered payloads and in-flight queue wholesale.
        Dropped contributors re-enter at their next launch; staleness
        weighting absorbs the lost partial round."""
        self.buffer = []
        self.pending = []
        self.buffer_ones = 0
        self._degraded_restore = True
        self._event("restore_degraded", client=int(meta["client"]),
                    round=int(meta["round"]), slot=int(slot))
        return self
