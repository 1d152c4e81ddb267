"""Minimal metrics registry with Prometheus text exposition.

Reference: the per-package prometheus metrics generated by
scripts/metricsgen (consensus/metrics.go:175 etc.), served at
node/node.go:630. Here: Counter / Gauge / Histogram with label support,
a process-local Registry, and ``render()`` producing the Prometheus
exposition format the RPC server serves at GET /metrics.
"""

from __future__ import annotations

import time
from bisect import bisect_right

from . import jsoncodec
from . import sync as libsync
from . import trace as libtrace


def escape_label_value(v: str) -> str:
    """Prometheus exposition escaping for label VALUES: backslash,
    double quote and newline (the spec's exact set — anything else is
    legal raw). Raw interpolation of e.g. a peer-supplied channel id
    containing '"' would tear the whole scrape."""
    return (
        v.replace("\\", "\\\\").replace("\n", "\\n").replace('"', '\\"')
    )


def _escape_help(v: str) -> str:
    """HELP text escaping: backslash and newline only (spec)."""
    return v.replace("\\", "\\\\").replace("\n", "\\n")


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help_: str, label_names: tuple[str, ...]):
        self.name = name
        self.help = help_
        self.label_names = label_names
        self._children: dict[tuple, "_Metric"] = {}
        self._mtx = libsync.Mutex("libs.metrics._mtx")

    def labels(self, *values: str) -> "_Metric":
        if len(values) != len(self.label_names):
            raise ValueError(
                f"{self.name}: expected {len(self.label_names)} labels"
            )
        key = tuple(str(v) for v in values)
        with self._mtx:
            child = self._children.get(key)
            if child is None:
                child = type(self)(self.name, self.help, ())
                child._label_values = key
                child._label_names_of_parent = self.label_names
                self._children[key] = child
            return child

    def remove(self, *values: str) -> bool:
        """Drop one labeled child series (e.g. a departed peer) so
        collector-maintained gauges keep bounded cardinality.  True if
        a series was removed."""
        key = tuple(str(v) for v in values)
        with self._mtx:
            return self._children.pop(key, None) is not None

    def _series(self):
        """Yield (label_suffix, self-or-child) leaf series."""
        if self.label_names:
            with self._mtx:
                children = list(self._children.items())
            for key, child in children:
                suffix = ",".join(
                    f'{n}="{escape_label_value(v)}"'
                    for n, v in zip(self.label_names, key)
                )
                yield "{" + suffix + "}", child
        else:
            yield "", self


class Counter(_Metric):
    kind = "counter"

    def __init__(self, name, help_="", label_names=()):
        super().__init__(name, help_, tuple(label_names))
        self._value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        with self._mtx:
            self._value += amount

    def value(self) -> float:
        with self._mtx:
            return self._value

    def _render_lines(self):
        for suffix, leaf in self._series():
            yield f"{self.name}{suffix} {leaf.value()}"


class Gauge(_Metric):
    kind = "gauge"

    def __init__(self, name, help_="", label_names=()):
        super().__init__(name, help_, tuple(label_names))
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._mtx:
            self._value = float(v)

    def inc(self, amount: float = 1.0) -> None:
        with self._mtx:
            self._value += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)

    def value(self) -> float:
        with self._mtx:
            return self._value

    def _render_lines(self):
        for suffix, leaf in self._series():
            yield f"{self.name}{suffix} {leaf.value()}"


class Histogram(_Metric):
    kind = "histogram"

    DEFAULT_BUCKETS = (
        0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0
    )

    def __init__(self, name, help_="", label_names=(), buckets=None):
        super().__init__(name, help_, tuple(label_names))
        self.buckets = tuple(buckets or self.DEFAULT_BUCKETS)
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._n = 0

    def observe(self, v: float) -> None:
        with self._mtx:
            self._counts[bisect_right(self.buckets, v)] += 1
            self._sum += v
            self._n += 1

    def _render_lines(self):
        for suffix, leaf in self._series():
            base = suffix[1:-1] if suffix else ""
            cum = 0
            for i, b in enumerate(leaf.buckets):
                cum += leaf._counts[i]
                lbl = f'le="{b}"' + (f",{base}" if base else "")
                yield f"{self.name}_bucket{{{lbl}}} {cum}"
            cum += leaf._counts[-1]
            lbl = 'le="+Inf"' + (f",{base}" if base else "")
            yield f"{self.name}_bucket{{{lbl}}} {cum}"
            tail = ("{" + base + "}") if base else ""
            yield f"{self.name}_sum{tail} {leaf._sum}"
            yield f"{self.name}_count{tail} {leaf._n}"

    def labels(self, *values):
        if len(values) != len(self.label_names):
            raise ValueError(
                f"{self.name}: expected {len(self.label_names)} labels"
            )
        key = tuple(str(v) for v in values)
        with self._mtx:
            child = self._children.get(key)
            if child is None:
                # construct with THIS histogram's buckets — the generic
                # child constructor would silently fall back to defaults
                child = Histogram(self.name, self.help, (), self.buckets)
                self._children[key] = child
            return child


class Registry:
    def __init__(self, namespace: str = "cometbft_tpu"):
        self.namespace = namespace
        self._metrics: list[_Metric] = []
        self._by_name: dict[str, _Metric] = {}
        self._mtx = libsync.Mutex("libs.metrics.Registry._mtx")

    def _register(self, metric: _Metric) -> _Metric:
        """Register a metric, deduplicating by name: two registrations
        of an identical metric return ONE instance (so independent
        subsystems can declare the same family), while a name collision
        with a different shape raises — duplicate ``# TYPE`` blocks are
        invalid exposition output."""
        with self._mtx:
            existing = self._by_name.get(metric.name)
            if existing is not None:
                if (
                    type(existing) is type(metric)
                    and existing.label_names == metric.label_names
                    and getattr(existing, "buckets", None)
                    == getattr(metric, "buckets", None)
                ):
                    return existing
                raise ValueError(
                    f"metric {metric.name!r} already registered with a "
                    f"different kind/labels/buckets"
                )
            self._by_name[metric.name] = metric
            self._metrics.append(metric)
        return metric

    def counter(self, name, help_="", label_names=()) -> Counter:
        return self._register(
            Counter(f"{self.namespace}_{name}", help_, label_names)
        )

    def gauge(self, name, help_="", label_names=()) -> Gauge:
        return self._register(
            Gauge(f"{self.namespace}_{name}", help_, label_names)
        )

    def histogram(self, name, help_="", label_names=(), buckets=None) -> Histogram:
        return self._register(
            Histogram(f"{self.namespace}_{name}", help_, label_names, buckets)
        )

    def render(self) -> str:
        """Prometheus text exposition format."""
        out = []
        with self._mtx:
            metrics = list(self._metrics)
        for m in metrics:
            if m.help:
                out.append(f"# HELP {m.name} {_escape_help(m.help)}")
            out.append(f"# TYPE {m.name} {m.kind}")
            out.extend(m._render_lines())
        return "\n".join(out) + "\n"


def quantile_from_buckets(buckets, counts, q: float) -> float:
    """Promql-style upper-bound quantile estimate from cumulative
    histogram buckets.

    ``buckets`` are the upper edges, ``counts`` the per-bucket tallies
    (length ``len(buckets) + 1`` — the trailing slot is the +Inf
    overflow, exactly a :class:`Histogram`'s ``_counts`` layout).  THE
    one p99-from-buckets estimator: libs/health's SLI engine, the
    netstats propagation view and the device-ledger starvation
    watchdog all call this — the math cannot fork between consumers.
    Values are read as a GIL-consistent snapshot; callers pass a
    ``list(h._counts)`` copy when the histogram is live."""
    total = 0
    for c in counts:
        total += c
    if total == 0:
        return 0.0
    rank = q * total
    cum = 0
    for i in range(len(counts) - 1):
        cum += counts[i]
        if cum >= rank:
            return float(buckets[i])
    return float(buckets[-1])  # everything above the top edge


# -- bounded-label exposition gate --------------------------------------
#
# Every labeled family this engine exports must keep a BOUNDED value
# space: channel ids come from a fixed enum, peers are capped top-K
# prefixes + "other", phases/outcomes/backends are code enums. A label
# fed from an unbounded input (full peer ids, tx hashes, heights)
# eventually blows up scrape size and Prometheus memory. The audit
# below is the exposition-side gate (run by the tier-1 observability
# tests against every live registry and both HTTP endpoints).

import re as _re

# label name -> value regex; anything matching no rule falls back to
# the per-family series cap only
_LABEL_VALUE_RULES: dict[str, "_re.Pattern"] = {
    "chID": _re.compile(r"^0x[0-9a-fA-F]{2}$"),
    "peer": _re.compile(r"^([0-9a-f]{1,10}|other)$"),
    "phase": _re.compile(r"^[a-z_]{1,24}$"),
    "direction": _re.compile(r"^[a-z2]{1,8}$"),
    "quantile": _re.compile(r"^(p\d{1,2}|last)$"),
    "reason": _re.compile(r"^[a-z_]{1,24}$"),
    # device-time ledger enums (libs/devledger): plane and caller come
    # from fixed tuples, stage from the budget-stage vocabulary
    # (libs/health.BUDGET_STAGES) — never from caller input
    "plane": _re.compile(r"^(verify|hash)$"),
    "caller": _re.compile(
        r"^(other|consensus-vote|commit-verify|proposal|light|mempool"
        r"|blocksync|evidence|merkle)$"
    ),
    # "stage" unions two fixed vocabularies: the budget stages
    # (libs/health.BUDGET_STAGES) and the tx-lifecycle plane's stage
    # and residency enums (libs/txtrace STAGE_NAMES / RESIDENCIES) —
    # never caller input, and sampled tx KEYS are never labels at all
    # (they export as bounded 16-hex-char prefixes in JSON bodies only)
    "stage": _re.compile(
        r"^(proposal_wait|gossip|verify_queue|verify_execute|hash"
        r"|wal_fsync|spec_exec|apply|residual"
        r"|admit|gossip_send|gossip_recv|proposal|commit"
        r"|admit_to_send|hop|admit_to_proposal|proposal_to_commit)$"
    ),
    # lock-contention plane (libs/lockprof): values are registry lock
    # names straight out of lockorder.json (dotted owner paths like
    # "consensus.wal._mtx"), bridged at scrape from the profiler
    # columns — bounded by the registry itself (~45-71 names); the
    # catch-all "other" slot is never exported. Shape check only here;
    # the true bound is the lockorder.json name set.  Uppercase is
    # legal: class-owned locks are class-qualified
    # ("state.indexer.KVBlockIndexer._mtx").
    "lock": _re.compile(r"^[A-Za-z0-9_.\-]{1,64}$"),
    # sampling-profiler plane (libs/profile): subsystem comes from the
    # fixed libs/profile.SUBSYSTEMS tuple ("p2p" has a digit), state
    # from the two-value on_cpu/blocked enum — never caller input
    "subsystem": _re.compile(r"^[a-z0-9_]{1,16}$"),
    "state": _re.compile(r"^(on_cpu|blocked)$"),
    # thread roles (libs/profile.role_of): a thread name without its
    # peer-id or ordinal suffix, cut to 32 of these characters; at most
    # 32 values, the rest folded into "other" (libs/profile._MAX_ROLES)
    "role": _re.compile(r"^[A-Za-z0-9_.\-]{1,32}$"),
}

# Explosion backstop, not a tight bound: the largest legitimate family
# is xla_compile_total's kernel x shape-bucket grid (~8 x 15). The
# shape rules above are the primary defense — they catch an unbounded
# value at the FIRST leak, long before the series count moves.
MAX_SERIES_PER_FAMILY = 256


def audit_label_cardinality(
    registry: "Registry", max_series: int = MAX_SERIES_PER_FAMILY
) -> list[str]:
    """Violations of the bounded-label contract: families whose child
    count exceeds ``max_series`` or whose label values break the
    per-label-name shape rules (e.g. a raw peer-id string leaking into
    a ``peer`` label). Empty list = clean."""
    out: list[str] = []
    with registry._mtx:
        metrics = list(registry._metrics)
    for m in metrics:
        if not m.label_names:
            continue
        with m._mtx:
            keys = list(m._children)
        if len(keys) > max_series:
            out.append(
                f"{m.name}: {len(keys)} series exceeds the "
                f"{max_series}-series bound"
            )
        for key in keys:
            for name, value in zip(m.label_names, key):
                rule = _LABEL_VALUE_RULES.get(name)
                if rule is not None and not rule.match(value):
                    out.append(
                        f"{m.name}{{{name}={value!r}}}: label value "
                        f"breaks the bounded-shape rule {rule.pattern}"
                    )
    return out


def scrape_duration_histogram(registry: "Registry") -> "Histogram":
    """The ``health_scrape_duration_seconds`` self-metric family.

    One definition shared by NodeMetrics and the exporters
    (libs/devstats.PrometheusServer, the /debug/devstats route) so the
    registry dedupe always resolves to the same instance — a scrape
    that slows down is itself an SLI."""
    return registry.histogram(
        "health_scrape_duration_seconds",
        "Wall time of one metrics scrape / devstats snapshot, by "
        "endpoint — the exporter's self-metric",
        label_names=("endpoint",),
        buckets=(
            0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
            0.25, 0.5, 1.0,
        ),
    )


class NodeMetrics:
    """The per-module metric structs a node wires through its services
    (consensus/metrics.go, p2p/metrics.go, mempool/metrics.go analogs)."""

    def __init__(self, registry: Registry | None = None):
        r = registry if registry is not None else Registry()
        self.registry = r
        # consensus
        self.height = r.gauge("consensus_height", "Committed chain height")
        self.rounds = r.gauge("consensus_rounds", "Round of the current height")
        self.validators = r.gauge(
            "consensus_validators", "Validator-set size"
        )
        self.block_interval = r.histogram(
            "consensus_block_interval_seconds",
            "Time between this and the last block",
        )
        self.block_txs = r.gauge(
            "consensus_num_txs", "Transactions in the latest block"
        )
        # verification (the TPU hot path)
        self.verify_batch_seconds = r.histogram(
            "crypto_verify_batch_seconds",
            "Batched signature verification latency",
            label_names=("backend",),
        )
        self.verify_batch_sigs = r.counter(
            "crypto_verify_batch_sigs_total",
            "Signatures verified through the batch path",
            label_names=("backend",),
        )
        # Phase breakdown of the SAME interval verify_batch_seconds
        # covers: pack (host staging + arena lookup), dispatch (async
        # kernel launch), readback (device materialization), fallback
        # (the host verifier). Buckets extend below the defaults so
        # millisecond-scale dispatch and pack phases resolve.
        self.verify_phase_seconds = r.histogram(
            "crypto_verify_phase_seconds",
            "Batched-verification per-phase latency",
            label_names=("phase", "backend"),
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        # one light-client header, phase by phase (libs/metrics.
        # light_phase): header (the whole verification, fetch included),
        # fetch (the provider's reply), valset_hash, header_basic,
        # sign_bytes. The last four do not nest in one another, so with
        # the verify phases above they tile a header; same buckets.
        # trusting (a skipping step's check of the trusted set's share,
        # light/verifier.verify_non_adjacent) holds the sign_bytes and
        # verify phases of its own commit check.
        self.light_verify_phase_seconds = r.histogram(
            "light_verify_phase_seconds",
            "Light-client header verification per-phase latency",
            label_names=("phase",),
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        # ValidatorSet.hash() calls by what answered: "computed" (the
        # leaves were encoded and the tree built) or "reused" (the set's
        # kept root, its witness checked). Both series exist from the
        # start, so a ratio over them reads 0 and not nothing.
        self.valset_hash_total = r.counter(
            "types_valset_hash_total",
            "ValidatorSet.hash() calls by result",
            label_names=("result",),
        )
        for result in ("computed", "reused"):
            self.valset_hash_total.labels(result)
        # ValidatorSet.key_types() calls by what answered: "computed" (the
        # keys' types scanned) or "reused" (the set's kept profile, its
        # witness checked). Both series exist from the start.
        self.valset_key_types_total = r.counter(
            "types_valset_key_types_total",
            "ValidatorSet.key_types() calls by result",
            label_names=("result",),
        )
        for result in ("computed", "reused"):
            self.valset_key_types_total.labels(result)
        # lanes whose sign-bytes a commit check encoded, by encoder:
        # "batched" (Commit.vote_sign_bytes_many, one encoding a commit)
        # or "per_lane" (canonical.vote_sign_bytes lane by lane: the
        # single-verify walk). Both series exist from the start.
        self.commit_sign_bytes_lanes_total = r.counter(
            "types_commit_sign_bytes_lanes_total",
            "Commit-check lanes by sign-bytes encoder",
            label_names=("path",),
        )
        for path in ("batched", "per_lane"):
            self.commit_sign_bytes_lanes_total.labels(path)
        # the attempts of a light client's bisection (light/client.py
        # _verify_skipping), by outcome: "verified" (the header came to
        # be trusted), "cant_trust" (too little of the trusted set signed
        # it: the client pivots) or "refused" (any other refusal)
        self.light_bisection_attempts_total = r.counter(
            "light_bisection_attempts_total",
            "Light-client bisection attempts by outcome",
            label_names=("outcome",),
        )
        for outcome in ("verified", "cant_trust", "refused"):
            self.light_bisection_attempts_total.labels(outcome)
        # the consensus vote path, phase by phase (libs/metrics.
        # consensus_phase): receive_vote / receive_data / receive_state
        # (the reactor's decode -> enqueue, one observation a message, on
        # the peer's thread); on the receive routine one observation a
        # drain each of drain (the whole micro-batch), queue_wait (the
        # drained votes' enqueue -> drain waits, summed), preverify (the
        # batched launch; sign_bytes inside it), wal_write, add_vote (the
        # VoteSets' admission), vote_step (what an admitted vote moves in
        # the FSM), block_part (proposal and parts), publish (deferred
        # event delivery: the HasVote fan-out) and timeout, each a sum
        # over the drain's items; finalize once a height. The phases
        # from preverify on do not nest in one another, so they tile a
        # drain. Same buckets as the verify phases.
        self.consensus_vote_phase_seconds = r.histogram(
            "consensus_vote_phase_seconds",
            "Consensus vote path per-phase latency",
            label_names=("phase",),
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        # a block fetched by block sync, phase by phase (libs/metrics.
        # blocksync_phase): block (the whole _apply_first, one a block
        # verified, refused ones too: the count the sums are divided by),
        # decode (the reactor's decode of a BlockResponse, on the
        # serving peer's thread), verify_light (the +2/3 check of the
        # first block by the second's LastCommit), part_set, store,
        # validate (apply_block's validation, the block's own LastCommit
        # in full inside) and apply (the rest of apply_block); wait (the
        # sync loop waiting for the next two blocks, one observation a
        # wait). verify_light, part_set, store, validate and apply nest
        # in no other and tile block. Same buckets as the verify phases.
        self.blocksync_phase_seconds = r.histogram(
            "blocksync_phase_seconds",
            "Block sync per-phase latency",
            label_names=("phase",),
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        # tocks the FSM handled: "acted" (it moved a step on one) or
        # "stale" (the round had gone on before it fired)
        self.consensus_timeouts_total = r.counter(
            "consensus_timeouts_total",
            "Consensus timeouts handled by the FSM, by what they did",
            label_names=("result",),
        )
        for result in ("acted", "stale"):
            self.consensus_timeouts_total.labels(result)
        # what the receive routine drained, by kind of item
        self.consensus_drain_items_total = r.counter(
            "consensus_drain_items_total",
            "Items the consensus receive routine drained, by kind",
            label_names=("kind",),
        )
        for kind in ("vote", "other"):
            self.consensus_drain_items_total.labels(kind)
        # the receive routine's own CPU inside its drains (two readings
        # of its thread's clock a drain, tracing on or off): over
        # consensus_vote_phase_seconds_sum{phase="drain"}, the share of
        # a drain's wall time spent on a CPU
        self.consensus_drain_cpu_seconds_total = r.counter(
            "consensus_drain_cpu_seconds_total",
            "CPU time of the consensus receive routine inside its drains",
        )
        # get_round_state() calls by what answered: "published" (the
        # snapshot the FSM's owner stored before it let the state mutex
        # go; no lock taken) or "locked" (the caller holds the mutex, or
        # nothing was published yet: a fresh copy under it). One tally a
        # call, handed over by the receive routine once a drain
        self.consensus_round_state_reads_total = r.counter(
            "consensus_round_state_reads_total",
            "ConsensusState.get_round_state() calls by path",
            label_names=("path",),
        )
        for path in ("published", "locked"):
            self.consensus_round_state_reads_total.labels(path)
        # lanes a drain pre-verified in one launch, by where the launch
        # ran (the verifier's own report: a coalescer window's route, or
        # the lone batch's side of the static cut)
        self.consensus_preverify_lanes_total = r.counter(
            "consensus_preverify_lanes_total",
            "Vote signatures pre-verified by the receive routine, by route",
            label_names=("route",),
        )
        for route in ("device", "host"):
            self.consensus_preverify_lanes_total.labels(route)
        # how a VoteSet's admission had a vote's signature answered:
        # "memo" (the drain's pre-verification, accept or refuse) or
        # "verified_singly" (no memo entry: one signature verified alone)
        self.vote_sig_admissions_total = r.counter(
            "types_vote_sig_admissions_total",
            "Vote signature checks at VoteSet admission by what answered",
            label_names=("answer",),
        )
        for answer in ("memo", "verified_singly"):
            self.vote_sig_admissions_total.labels(answer)
        # how a precommit's extension signature was answered, at the
        # pre-app check of ConsensusState._add_vote and at VoteSet
        # admission: "memo" (the drain's pre-verification, accept or
        # refuse) or "verified_singly" (no entry: a verify of its own)
        self.extension_sig_checks_total = r.counter(
            "consensus_extension_sig_checks_total",
            "Vote extension signature checks by what answered",
            label_names=("answer",),
        )
        for answer in ("memo", "verified_singly"):
            self.extension_sig_checks_total.labels(answer)
        # how ops/verify.pack_bytes built a wire buffer: "columnar" (key,
        # signature and message columns joined by C-level calls and one
        # native call that writes the transposed rows) or "per_lane" (a
        # malformed lane sent the packer through its per-lane pass, or
        # there is no native engine and the pure-Python loop ran)
        self.verify_pack_lanes_total = r.counter(
            "crypto_verify_pack_lanes_total",
            "Lanes packed for the device verifier by packer path",
            label_names=("path",),
        )
        for path in ("columnar", "per_lane"):
            self.verify_pack_lanes_total.labels(path)
        # how the device key arena answered a lookup: "memo" (the whole
        # key column was the one a kept slot vector was computed for),
        # "walked" (key by key through the LRU) or "uncached" (the lookup
        # gave up: more distinct keys than slots, or eviction churn won
        # three builds; the lanes go to the uncached kernel)
        self.pubkey_lookup_lanes_total = r.counter(
            "ops_pubkey_lookup_lanes_total",
            "Key-arena lookup lanes by how their slots were found",
            label_names=("result",),
        )
        for result in ("memo", "walked", "uncached"):
            self.pubkey_lookup_lanes_total.labels(result)
        # keys the arena's builder launches expanded into tables (the
        # launch bucket's padding not counted)
        self.pubkey_tables_built_total = r.counter(
            "ops_pubkey_tables_built_total",
            "Expanded pubkey tables built for the device key arena",
        )
        # cross-caller verify coalescer (crypto/coalesce.py): window
        # shape, flush triggers, and the submit-to-verdict latency its
        # callers actually pay. Lane-count buckets are the shape
        # buckets the device launches use; wait buckets extend under a
        # millisecond like verify_phase_seconds (the deadline window
        # is ~500 us).
        self.coalesce_window_lanes = r.histogram(
            "crypto_coalesce_window_lanes",
            "Lanes per coalesced verify window",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048),
        )
        self.coalesce_wait_seconds = r.histogram(
            "crypto_coalesce_wait_seconds",
            "Submit-to-verdict latency of coalesced verify tickets",
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        self.coalesce_flushes = r.counter(
            "crypto_coalesce_flush_total",
            "Coalescer window flushes by trigger",
            label_names=("reason",),
        )
        # what each window's lanes ran on, and how long a ticket stood
        # in the queue (submit on the caller's thread -> the window's
        # pop on the executor's; the coalesce.queue_wait span reads the
        # same interval). Both route series exist from the start, so a
        # share over them reads 0 and not nothing.
        self.coalesce_lanes = r.counter(
            "crypto_coalesce_lanes_total",
            "Lanes of resolved coalescer windows by route",
            label_names=("route",),
        )
        for route in ("device", "host"):
            self.coalesce_lanes.labels(route)
        self.coalesce_queue_wait_seconds = r.histogram(
            "crypto_coalesce_queue_wait_seconds",
            "Submit-to-window-pop wait of coalesced verify tickets",
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        # cross-caller hash plane (crypto/hashplane.py): window shape,
        # flush triggers, and the per-phase latency of the SHA-256
        # pipeline (dispatch = pack + async launch, readback = device
        # materialization, fallback = host hashlib buckets). Mirrors
        # the coalescer families above — one dashboard reads both
        # planes the same way.
        self.hash_window_lanes = r.histogram(
            "crypto_hash_window_lanes",
            "Lanes per coalesced hash window",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048),
        )
        self.hash_flushes = r.counter(
            "crypto_hash_flush_total",
            "Hash-plane window flushes by trigger",
            label_names=("reason",),
        )
        self.hash_phase_seconds = r.histogram(
            "crypto_hash_phase_seconds",
            "Hash-plane per-phase latency",
            label_names=("phase",),
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        # device-time ledger (libs/devledger): per-(plane, caller)
        # attribution of the shared coalescer planes. The time/lane
        # counters are bridged from the lock-free ledger columns at
        # scrape (devledger.sample — the devstats replay pattern); the
        # queue-wait histogram is observed live on the resolve path
        # WHILE the ledger is enabled (COMETBFT_TPU_LEDGER=0 darkens
        # the whole accounting block in one flag check), and its
        # consensus-caller buckets feed the starvation watchdog's p99
        # (libs/health). plane/caller/stage label values are fixed
        # enums, gated by audit_label_cardinality.
        self.device_time = r.counter(
            "device_time_seconds_total",
            "Device-plane time attributed per caller class, pro-rata "
            "by lane count over each window's execute (device) or "
            "fallback (host) duration",
            label_names=("plane", "caller"),
        )
        self.device_lanes = r.counter(
            "device_lanes_total",
            "Lanes submitted through the coalescer planes per caller "
            "class",
            label_names=("plane", "caller"),
        )
        self.device_queue_wait = r.histogram(
            "device_queue_wait_seconds",
            "Submit-to-launch queue wait of coalesced tickets, by "
            "plane and caller class (execute time excluded — this is "
            "pure queueing delay)",
            label_names=("plane", "caller"),
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        self.height_budget = r.gauge(
            "height_budget_seconds",
            "Latency budget of the most recent fully-decomposed "
            "committed height, by stage (libs/health.budget: "
            "proposal_wait/gossip/verify_queue/verify_execute/hash/"
            "wal_fsync/apply/residual)",
            label_names=("stage",),
        )
        # lock-contention plane (libs/lockprof): per-lock wait/hold
        # seconds and contended-acquire counts, bridged at scrape from
        # the lock-free profiler columns (lockprof.sample — the
        # netstats/devledger replay pattern; the record path itself
        # never touches these families). The "lock" label value space
        # is the lockorder.json registry name set — bounded, audited
        # by the "lock" shape rule above.
        self.lock_wait = r.counter(
            "lock_wait_seconds_total",
            "Time threads spent blocked acquiring each named engine "
            "lock (contended acquires only; uncontended fast-path "
            "acquires contribute zero)",
            label_names=("lock",),
        )
        self.lock_hold = r.counter(
            "lock_hold_seconds_total",
            "Time each named engine lock was held (outermost "
            "acquire-to-release; reentrant RLock re-entries excluded)",
            label_names=("lock",),
        )
        self.lock_contended = r.counter(
            "lock_contended_acquires_total",
            "Acquires of each named engine lock that had to block "
            "because another thread held it",
            label_names=("lock",),
        )
        # sampling-profiler plane (libs/profile): per-(subsystem,
        # state) sample counts, bridged at scrape from the profiler's
        # lock-free counter columns (profile.sample — the same
        # watermark pattern as the lockprof bridge above). Both label
        # value spaces are fixed enums, audited by the "subsystem" and
        # "state" shape rules.
        self.profile_samples = r.counter(
            "profile_samples_total",
            "Sampling-profiler samples by subsystem and execution "
            "state (on_cpu vs blocked; ~67 Hz default, "
            "COMETBFT_TPU_PROF_HZ)",
            label_names=("subsystem", "state"),
        )
        # the kernel's CPU clock of every live thread, read by the
        # profiler's sampler thread (4 Hz and at stop) and summed by
        # role: the thread name without its peer-id suffix, bounded to
        # 32 values by libs/profile and audited by the "role" shape
        # rule. Bridged by profile.sample (scrape, and once a drain)
        self.thread_cpu_seconds = r.counter(
            "thread_cpu_seconds_total",
            "Kernel CPU time of the process's threads by role (thread "
            "name without its peer-id suffix), while the sampling "
            "profiler runs",
            label_names=("role",),
        )
        # time inside top-level libs/jsoncodec Codec.encode / dumps
        # calls, every codec of the process (storage records, WAL
        # records, wire messages), counted from when this registry was
        # made. Bridged by observe_codec_encode once a synced block
        self.codec_encode_seconds = r.counter(
            "codec_encode_seconds_total",
            "Time inside top-level storage/wire codec encodes "
            "(Codec.encode and Codec.dumps of libs/jsoncodec)",
        )
        self._codec_encode_wm = jsoncodec.encode_ns()
        # light-client proof service (light/service.py): request
        # outcomes, the commit-verification result cache, and the
        # backpressure surface (in-flight occupancy + slot queue wait).
        self.light_requests = r.counter(
            "light_service_requests_total",
            "Light-service verification requests by outcome (ok / "
            "error / rejected=backpressure / deadline / stopped)",
            label_names=("outcome",),
        )
        self.light_cache_lookups = r.counter(
            "light_service_cache_hits_total",
            "Commit-verification result-cache lookups by outcome: hit "
            "(cached success), miss (this caller verified), shared "
            "(single-flight wait on a concurrent identical verify)",
            label_names=("outcome",),
        )
        self.light_inflight = r.gauge(
            "light_service_inflight",
            "Light-service requests currently holding an in-flight slot",
        )
        self.light_queue_wait = r.histogram(
            "light_service_queue_wait_seconds",
            "Time an admitted light-service request waited for an "
            "in-flight slot (rejections don't observe here — they "
            "count in light_service_requests_total{outcome=rejected})",
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        # device/compilation telemetry (libs/devstats): the XLA side of
        # the verify pipeline. Families exist on every node registry so
        # a scrape always returns them; devstats populates them only
        # when COMETBFT_TPU_DEVSTATS (or a Prometheus listener) is on.
        self.xla_compiles = r.counter(
            "xla_compile_total",
            "XLA kernel compilations observed in-process",
            label_names=("kernel", "bucket"),
        )
        self.xla_compile_seconds = r.histogram(
            "xla_compile_seconds",
            "Wall time of each XLA compilation (trace+lower+compile, "
            "measured at the first dispatch of a kernel x bucket)",
            label_names=("kernel",),
            buckets=(
                0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                120.0, 300.0,
            ),
        )
        self.xla_recompiles = r.counter(
            "xla_recompile_total",
            "Compilations of an ALREADY-compiled kernel x bucket — "
            "steady-state recompiles; any nonzero rate is a bug "
            "(shape-bucket leak or dtype drift)",
        )
        self.xla_cache = r.counter(
            "xla_cache_hit_total",
            "Persistent compilation-cache outcome per compile",
            label_names=("outcome",),
        )
        self.device_memory = r.gauge(
            "device_memory_bytes",
            "device.memory_stats() byte gauges, sampled at scrape",
            label_names=("device", "kind"),
        )
        self.arena_slots = r.gauge(
            "pubkey_arena_slots",
            "Expanded-pubkey device arena slot occupancy",
            label_names=("state",),
        )
        self.arena_lookups = r.counter(
            "pubkey_arena_lookups_total",
            "Arena key lookups by result",
            label_names=("result",),
        )
        self.arena_builds = r.counter(
            "pubkey_arena_builds_total",
            "Arena builder launches (device round trips for new keys)",
        )
        self.arena_evictions = r.counter(
            "pubkey_arena_evictions_total", "Arena LRU evictions"
        )
        self.transfer_bytes = r.counter(
            "device_transfer_bytes_total",
            "Host<->device bytes shipped at the pack/readback edges",
            label_names=("direction",),
        )
        self.transfer_ops = r.counter(
            "device_transfer_ops_total",
            "Host<->device transfer operations (launches, readbacks)",
            label_names=("direction",),
        )
        self.validators_power = r.gauge(
            "consensus_validators_power", "Total validator voting power"
        )
        self.missing_validators = r.gauge(
            "consensus_missing_validators",
            "Validators absent from the last commit",
        )
        self.missing_validators_power = r.gauge(
            "consensus_missing_validators_power",
            "Voting power absent from the last commit",
        )
        self.byzantine_validators = r.gauge(
            "consensus_byzantine_validators",
            "Validators with committed evidence against them",
        )
        self.total_txs = r.counter(
            "consensus_total_txs", "Transactions committed since boot"
        )
        self.block_size = r.gauge(
            "consensus_block_size_bytes", "Size of the latest block"
        )
        self.round_duration = r.histogram(
            "consensus_round_duration_seconds",
            "Time spent in each consensus round",
        )
        self.step_duration = r.histogram(
            "consensus_step_duration_seconds",
            "Time spent in each consensus step",
            label_names=("step",),
        )
        self.late_votes = r.counter(
            "consensus_late_votes",
            "Votes received for an earlier height",
            label_names=("vote_type",),
        )
        self.duplicate_votes = r.counter(
            "consensus_duplicate_vote_total",
            "Conflicting (equivocating) votes observed",
        )
        self.proposals = r.counter(
            "consensus_proposal_receive_count",
            "Proposals received",
            label_names=("status",),
        )
        # pipelined commits (consensus/pipeline): speculation outcomes
        # + how far the durable (fsynced) height trails the FSM height.
        # Outcome values come from the fixed hit/miss/abort enum, so
        # cardinality is bounded by construction.
        self.spec_exec = r.counter(
            "consensus_spec_exec_total",
            "Speculative block executions by outcome (hit = memoized "
            "result consumed at commit, miss = committed block was "
            "never speculated, abort = speculation discarded unused)",
            label_names=("outcome",),
        )
        self.fsync_lag_heights = r.gauge(
            "consensus_fsync_lag_heights",
            "Heights the FSM has committed in memory but whose "
            "save-block + WAL fsync the commit-writer has not yet made "
            "durable (0 = fully drained / serial mode)",
        )
        # p2p (the network-plane telemetry layer, libs/netstats): every
        # label here is BOUNDED by construction — chID values come from
        # the fixed channel enum, peer values are 10-char node-id
        # prefixes capped at top-K + "other" by the netstats collector,
        # phase values from netstats.PHASES. audit_label_cardinality
        # gates this contract at test time.
        self.peers = r.gauge("p2p_peers", "Connected peers")
        self.p2p_send_bytes = r.counter(
            "p2p_message_send_bytes_total",
            "Bytes sent per logical channel (frame bytes incl. the "
            "5-byte packet header; sums across peers)",
            label_names=("chID",),
        )
        self.p2p_recv_bytes = r.counter(
            "p2p_message_receive_bytes_total",
            "Bytes received per logical channel (frame bytes incl. "
            "the 5-byte packet header; sums across peers)",
            label_names=("chID",),
        )
        self.p2p_msgs_sent = r.counter(
            "p2p_messages_sent_total",
            "Complete messages sent per logical channel (counted at "
            "the eof packet)",
            label_names=("chID",),
        )
        self.p2p_msgs_recv = r.counter(
            "p2p_messages_received_total",
            "Complete messages received per logical channel",
            label_names=("chID",),
        )
        self.p2p_send_queue_full = r.counter(
            "p2p_send_queue_full_total",
            "Messages dropped because MConnection.send timed out on a "
            "full bounded send queue — every drop is logged and "
            "trace-attributable (p2p.drop events)",
            label_names=("chID",),
        )
        self.p2p_send_queue_depth = r.gauge(
            "p2p_send_queue_depth",
            "Send-queue occupancy per channel, summed across peers "
            "(sampled at scrape by the netstats collector)",
            label_names=("chID",),
        )
        self.p2p_send_queue_hwm = r.gauge(
            "p2p_send_queue_highwater",
            "Highest send-queue depth any peer's channel reached "
            "since connect (max across live peers)",
            label_names=("chID",),
        )
        self.p2p_peer_rate = r.gauge(
            "p2p_peer_rate_bytes",
            "Per-peer flowrate EMA in bytes/s; peer label is a "
            "10-char node-id prefix for the top-K peers by traffic "
            "plus an 'other' aggregate (COMETBFT_TPU_NET_TOPK)",
            label_names=("peer", "direction"),
        )
        # NTP-style clock-offset estimate per stamped top-K peer, from
        # netstamp round-trip pairs (libs/netstats): offset = t2 -
        # (t1+t3)/2 of the tightest CONSISTENT round trip (crossed
        # pairings that would fake a tiny rt are rejected against the
        # causality-free floor offset >= t2 - t3), positive = peer
        # clock ahead of ours. The matching bound (+-rt/2) rides in
        # its own family so alerts can fire on |offset| >
        # bound-tolerance. Series are removed with the peer (same
        # lifecycle as p2p_peer_rate_bytes), keeping the label bounded.
        self.p2p_peer_clock_skew = r.gauge(
            "p2p_peer_clock_skew_seconds",
            "Estimated wall-clock offset to each stamped top-K peer "
            "(netstamp round-trip pairs; positive = peer ahead); the "
            "bound is p2p_peer_clock_skew_bound_seconds",
            label_names=("peer",),
        )
        self.p2p_peer_clock_skew_bound = r.gauge(
            "p2p_peer_clock_skew_bound_seconds",
            "+-bound of the peer clock-skew estimate (half the "
            "tightest consistent stamped round trip)",
            label_names=("peer",),
        )
        # Gray-failure defense (p2p/suspicion): peers evicted because
        # their netstats signals (sustained send-queue-full streaks,
        # stamp staleness, propagation-lag outliers) crossed the
        # suspicion threshold. The reason label is a fixed enum of the
        # dominant signal; the eviction goes through the ordinary
        # switch machinery, so persistent peers reconnect fresh.
        self.p2p_suspicion_evictions = r.counter(
            "p2p_suspicion_evictions_total",
            "Peers evicted by the peer-health suspicion scorer, by "
            "dominant signal (queue_full/stale/lag/mixed)",
            label_names=("reason",),
        )
        self.p2p_suspect_peers = r.gauge(
            "p2p_suspect_peers",
            "Peers currently carrying nonzero suspicion (scored by "
            "p2p/suspicion from netstats signals; decays to zero "
            "while a peer behaves)",
        )
        # One-hop gossip propagation lag derived from provenance
        # stamps (libs/netstats): origin wall hint -> local receive,
        # attributed per consensus phase by the reactors. Sub-ms
        # buckets — in-process nets propagate in tens of microseconds.
        self.p2p_propagation = r.histogram(
            "p2p_propagation_seconds",
            "One-hop message propagation lag by consensus phase "
            "(proposal/block_part/prevote/precommit/commit/block/tx), "
            "from provenance stamps; cross-host values carry clock "
            "skew",
            label_names=("phase",),
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        # mempool
        self.mempool_size = r.gauge("mempool_size", "Pending transactions")
        self.mempool_failed_txs = r.counter(
            "mempool_failed_txs", "CheckTx rejections"
        )
        self.mempool_tx_size = r.histogram(
            "mempool_tx_size_bytes", "Transaction sizes at CheckTx"
        )
        self.mempool_rechecks = r.counter(
            "mempool_recheck_times", "Transactions rechecked after commits"
        )
        # tx-lifecycle plane (libs/txtrace): deterministic hash-sampled
        # end-to-end transaction tracing. Families are BRIDGED at
        # scrape from the plane's lock-free completion ring
        # (txtrace.sample, called by libs/health.sample — the devledger
        # watermark pattern; the record path touches no metrics
        # object). Buckets span in-process test nets (~tens of ms) up
        # to production multi-second commit cadences.
        self.tx_commit_latency = r.histogram(
            "tx_commit_latency_seconds",
            "End-to-end submit-to-commit latency of sampled "
            "transactions (mempool admission to the committed block)",
            buckets=(
                0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                5.0, 10.0, 30.0, 60.0,
            ),
        )
        self.tx_stage_seconds = r.histogram(
            "tx_stage_seconds",
            "Per-stage residency of sampled transactions: "
            "admit_to_send (admission to first gossip send), hop "
            "(stamped one-hop receive lag), admit_to_proposal "
            "(mempool residency), proposal_to_commit (consensus time)",
            label_names=("stage",),
            buckets=(
                0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
            ),
        )
        self.tx_sampled = r.counter(
            "tx_sampled_total",
            "Sampled-transaction stage records by lifecycle stage "
            "(admit/gossip_send/gossip_recv/proposal/commit)",
            label_names=("stage",),
        )
        self.mempool_oldest_age = r.gauge(
            "mempool_oldest_age_seconds",
            "Age of the oldest admitted-uncommitted transaction in "
            "the mempool (0 when empty) — the tx_starved watchdog's "
            "signal",
        )
        # health/SLO engine (libs/health): SLIs derived at scrape time
        # from the always-on flight recorder + existing families, plus
        # the watchdog trip counter and the composite score.
        self.health_score = r.gauge(
            "health_score",
            "Composite node health in [0,1]: 1 healthy; a consensus "
            "stall zeroes it; an open verify breaker or an active "
            "recompile storm each subtract 0.3",
        )
        self.health_commit_latency = r.gauge(
            "health_commit_latency_seconds",
            "Per-height commit latency derived from the flight "
            "recorder's recent window",
            label_names=("quantile",),
        )
        self.health_rounds_per_height = r.gauge(
            "health_rounds_per_height",
            "Mean rounds needed per committed height (recent window); "
            "1.0 is a healthy net",
        )
        self.health_verify_wait_p99 = r.gauge(
            "health_verify_wait_seconds",
            "p99 submit-to-verdict wait of coalesced verifies "
            "(upper-bound estimate from crypto_coalesce_wait_seconds)",
        )
        self.health_breaker_open = r.gauge(
            "health_breaker_open",
            "1 while the verify coalescer's half-open breaker keeps "
            "routing off (wedged device executor)",
        )
        self.health_wal_fsync = r.gauge(
            "health_wal_fsync_seconds",
            "p99 WAL fsync latency from the flight recorder's recent "
            "window",
        )
        self.health_stall_seconds = r.gauge(
            "health_stall_seconds",
            "Seconds since the last consensus step transition",
        )
        self.health_gossip_lag = r.gauge(
            "health_gossip_lag_seconds",
            "p99 one-hop gossip propagation lag over the recent "
            "stamped-message window (libs/netstats; 0 until stamped "
            "traffic arrives)",
        )
        self.health_watchdog_trips = r.counter(
            "health_watchdog_trips_total",
            "Watchdog trips by detector: consensus_stall / "
            "verify_breaker / recompile_storm",
            label_names=("watchdog",),
        )
        self.health_bundles = r.counter(
            "health_bundle_writes_total",
            "Black-box bundles written by tripped watchdogs "
            "(rate-limited)",
        )
        self.health_scrape_seconds = scrape_duration_histogram(r)


# Process-wide node-metrics STACK, pushed by node assembly and popped
# on node stop; instrumentation points (e.g. the batch verifiers, which
# are constructed deep inside VoteSet admission with no node handle)
# observe through node_metrics() and never branch.  A stack — not a
# single slot — so in-process multi-node test nets degrade gracefully:
# the most recently started node receives the process-wide
# observations, and stopping it RESTORES the previous node's registry
# instead of silently dropping every later observation into the no-op
# sink (the documented last-node-wins flaw this replaces).  Pops are
# by identity, so out-of-order node shutdown cannot evict a live node.
_NODE_METRICS_STACK: list[NodeMetrics] = []

_NOP_METRICS: NodeMetrics | None = None


def push_node_metrics(m: NodeMetrics) -> None:
    """Install ``m`` as the process-wide observation target (node boot)."""
    _NODE_METRICS_STACK.append(m)


def pop_node_metrics(m: NodeMetrics) -> None:
    """Remove ``m`` wherever it sits in the stack (node stop); the
    remaining top — if any — becomes the target again."""
    for i in range(len(_NODE_METRICS_STACK) - 1, -1, -1):
        if _NODE_METRICS_STACK[i] is m:
            del _NODE_METRICS_STACK[i]
            return


def node_metrics() -> NodeMetrics:
    """The running node's metrics, or a throwaway sink when no node is
    up (library use, tests) — instrumentation sites never branch."""
    global _NOP_METRICS
    if _NODE_METRICS_STACK:
        return _NODE_METRICS_STACK[-1]
    if _NOP_METRICS is None:
        _NOP_METRICS = NodeMetrics(Registry())
    return _NOP_METRICS


def observe_verify_phase(
    phase: str,
    backend: str,
    seconds: float,
    lanes: int,
    arena: str | None = None,
) -> None:
    """One verify-pipeline phase: Prometheus histogram + trace event.

    The SAME breakdown lands in both sinks — Prometheus gets
    ``crypto_verify_phase_seconds{phase,backend}``, the tracer gets a
    ``verify.<phase>`` event with lane count and (device paths) the
    pubkey-arena disposition — so a BENCH row and a production scrape
    attribute a regression to the same phase. Trace fields are only
    built when tracing is on; the histogram is part of the always-on
    metrics layer, same as crypto_verify_batch_seconds.
    """
    node_metrics().verify_phase_seconds.labels(phase, backend).observe(
        seconds
    )
    if libtrace.enabled():
        if arena is None:
            libtrace.event(
                "verify." + phase,
                backend=backend,
                lanes=lanes,
                dur_ns=int(seconds * 1e9),
            )
        else:
            libtrace.event(
                "verify." + phase,
                backend=backend,
                lanes=lanes,
                arena=arena,
                dur_ns=int(seconds * 1e9),
            )


class TimedPhase:
    """``with`` block that times one phase for two sinks from one pair
    of clock readings: ``hist`` (always on; None = the caller sums
    ``dur_ns`` over several blocks and observes once) and, while
    tracing is on, a libs/trace span of the same interval (``parent``:
    a span of another thread that the phase belongs to). One per
    phase, never per lane."""

    __slots__ = ("_hist", "_span", "_t0", "dur_ns")

    def __init__(self, hist, span_name: str, parent=None, **fields):
        self._hist = hist
        self._span = libtrace.span(span_name, parent, **fields)
        self.dur_ns = 0

    def set(self, **fields) -> None:
        """Span fields known only inside the block (libtrace.Span.set)."""
        self._span.set(**fields)

    def __enter__(self) -> "TimedPhase":
        if self._span is libtrace.NOP_SPAN:
            self._t0 = time.time_ns()  # the clock libs/trace reads
        else:
            self._span.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        sp = self._span
        if sp is libtrace.NOP_SPAN:
            self.dur_ns = max(0, time.time_ns() - self._t0)
        else:
            sp.__exit__(*exc)
            self.dur_ns = sp.dur_ns
        if self._hist is not None:
            self._hist.observe(self.dur_ns / 1e9)


def light_phase(phase: str, span_name: str, **fields) -> TimedPhase:
    """One phase of a light-client header: ``light_verify_phase_seconds
    {phase}`` and the ``span_name`` span."""
    return TimedPhase(
        node_metrics().light_verify_phase_seconds.labels(phase),
        span_name, **fields,
    )


def consensus_phase(phase: str, span_name: str, **fields) -> TimedPhase:
    """One phase of the consensus vote path: ``consensus_vote_phase_seconds
    {phase}`` and the ``span_name`` span."""
    return TimedPhase(
        node_metrics().consensus_vote_phase_seconds.labels(phase),
        span_name, **fields,
    )


def blocksync_phase(phase: str, span_name: str, **fields) -> TimedPhase:
    """One phase of block sync: ``blocksync_phase_seconds{phase}`` and
    the ``span_name`` span."""
    return TimedPhase(
        node_metrics().blocksync_phase_seconds.labels(phase),
        span_name, **fields,
    )


def verify_phase(phase: str, backend: str, **fields) -> TimedPhase:
    """One phase of a batch verify: ``crypto_verify_phase_seconds{phase,
    backend}`` and the ``verify.<phase>`` span (field ``backend``)."""
    return TimedPhase(
        node_metrics().verify_phase_seconds.labels(phase, backend),
        "verify." + phase, backend=backend, **fields,
    )


def observe_consensus_phase_sum(phase: str, span_name: str, ns: int,
                                n: int, event: bool = True) -> None:
    """A phase that recurs per item of a drain (a WAL write, an
    admission), summed over the drain: one histogram observation of the
    sum, and one after-the-fact ``span_name`` event under the drain's
    span (``dur_ns``, ``n``): a span an item would fill the ring."""
    node_metrics().consensus_vote_phase_seconds.labels(phase).observe(
        ns / 1e9
    )
    if event and libtrace.enabled():
        libtrace.event(span_name, dur_ns=ns, n=n)


def observe_vote_admission(answer: str) -> None:
    """One signature check at VoteSet admission:
    types_vote_sig_admissions_total{answer}."""
    node_metrics().vote_sig_admissions_total.labels(answer).inc()


def observe_extension_sig_check(answer: str) -> None:
    """One extension-signature check, before the app's
    VerifyVoteExtension or at VoteSet admission:
    consensus_extension_sig_checks_total{answer}."""
    node_metrics().extension_sig_checks_total.labels(answer).inc()


def observe_drain_cpu(ns: int) -> None:
    """``ns`` of the receive routine's CPU in one drain:
    consensus_drain_cpu_seconds_total."""
    node_metrics().consensus_drain_cpu_seconds_total.inc(ns / 1e9)


def observe_round_state_reads(path: str, n: int) -> None:
    """``n`` more ``ConsensusState.get_round_state()`` calls answered by
    ``path``: consensus_round_state_reads_total{path}. The calls tally
    themselves with no lock; the receive routine hands the tally over
    once a drain."""
    node_metrics().consensus_round_state_reads_total.labels(path).inc(n)


def observe_codec_encode(metrics: NodeMetrics | None = None) -> None:
    """The codec's encode time since the last call:
    codec_encode_seconds_total. The encodes add to a process-wide sum
    with no lock; one thread hands the sum over (the sync loop, once a
    block)."""
    m = metrics if metrics is not None else node_metrics()
    now = jsoncodec.encode_ns()
    if now > m._codec_encode_wm:
        m.codec_encode_seconds.inc((now - m._codec_encode_wm) / 1e9)
        m._codec_encode_wm = now


def observe_valset_hash(reused: bool) -> None:
    """One ``ValidatorSet.hash()`` call: types_valset_hash_total{result}."""
    node_metrics().valset_hash_total.labels(
        "reused" if reused else "computed"
    ).inc()


def observe_valset_key_types(reused: bool) -> None:
    """One ``ValidatorSet.key_types()`` call:
    types_valset_key_types_total{result}."""
    node_metrics().valset_key_types_total.labels(
        "reused" if reused else "computed"
    ).inc()


def observe_commit_sign_bytes(path: str, lanes: int) -> None:
    """Lanes one commit check encoded sign-bytes for:
    types_commit_sign_bytes_lanes_total{path}."""
    node_metrics().commit_sign_bytes_lanes_total.labels(path).inc(lanes)


def _set_on_pack_span(**fields) -> None:
    """Fields for the ``verify.pack`` span the caller runs under, if it
    runs under one (verify_batch's and the coalescer's both do)."""
    sp = libtrace.current()
    if sp is not None and sp.name == "verify.pack":
        sp.set(**fields)


def observe_pack_lanes(path: str, lanes: int) -> None:
    """One ops/verify.pack_bytes call: crypto_verify_pack_lanes_total
    {path}, and ``path`` on the enclosing ``verify.pack`` span."""
    node_metrics().verify_pack_lanes_total.labels(path).inc(lanes)
    _set_on_pack_span(path=path)


def observe_pubkey_lookup(result: str, lanes: int) -> None:
    """One key-arena lookup: ops_pubkey_lookup_lanes_total{result}, and
    ``slots`` on the enclosing ``verify.pack`` span."""
    node_metrics().pubkey_lookup_lanes_total.labels(result).inc(lanes)
    _set_on_pack_span(slots=result)


def observe_tables_built(keys: int) -> None:
    """One builder launch of the key arena: ops_pubkey_tables_built_total."""
    node_metrics().pubkey_tables_built_total.inc(keys)


def observe_bisection_attempt(outcome: str) -> None:
    """One attempt of a light client's bisection:
    light_bisection_attempts_total{outcome}."""
    node_metrics().light_bisection_attempts_total.labels(outcome).inc()


def observe_hash_phase(phase: str, seconds: float, lanes: int) -> None:
    """One hash-plane phase: Prometheus histogram + trace event, the
    observe_verify_phase analog for crypto/hashplane — a scrape and a
    trace attribute a hash regression to the same phase."""
    node_metrics().hash_phase_seconds.labels(phase).observe(seconds)
    if libtrace.enabled():
        libtrace.event(
            "hash." + phase,
            lanes=lanes,
            dur_ns=int(seconds * 1e9),
        )
