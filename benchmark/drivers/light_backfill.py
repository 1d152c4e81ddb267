"""Closed-loop backfill through the light service: N relayer workers, each
asking ``LightService.verify_at_height(target, trust_height)`` and issuing
its next request when the last returned (they stand where the RPC server's
handler threads stand). The service runs on its defaults with its own
coalescer; every request builds a fresh ``light.Client`` over the shared
commit-verification plane.

No height is touched by two requests of a run, so no cache can answer: a
sound request costs the root's own +2/3 check, the +1/3 trusting check of
the target's commit against the root's set, and the target's own +2/3
check. A reserved share of the requests names a root hash that is not the
chain's (refused before any signature is looked at) or asks for a target
whose commit has one counted lane altered (refused naming the lane). What
the service answered is compared, request by request, with the plain
reference's verdict; the lanes the program's verifiers counted are compared
with the lanes the reference says those requests needed.
"""

from __future__ import annotations

import itertools
import random
import sys
import threading
import time

import numpy as np

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.light.errors import LightClientError
from cometbft_tpu.light.service import LightService
from cometbft_tpu.ops import verify as ov

from ..harness import chain as rawchain
from ..harness import stats, tracing
from ..reference import light_ref, skipping_ref
from . import adapters, verdicts

SIGS = "prom.cometbft_tpu_crypto_verify_batch_sigs_total{"
COALESCE_LANES = "prom.cometbft_tpu_crypto_coalesce_lanes_total{"
_ROOT_MISMATCH = "trusted header hash mismatch"
EXIT_CANNOT_SERVE = 5


def preflight_staging(stagers: int = 4) -> None:
    """This deployment has 64 callers launching rows of one shape at once.
    Where the program's verify plane stages a launch's rows in a lane arena,
    a staged buffer has to outlive every staging of the same shape that
    happens before its own launch. A program that hands the buffer on (two
    slots a shape, the older donated to the next staging) loses whole
    commit checks under this traffic: its requests fail by the dozen and
    the run is not correct whatever is measured. That is said here, before
    any set-up, by exit code 5 and no result line. A program with no arena
    has nothing to lose and goes on."""
    arena = getattr(ov, "_LANE_ARENA", None)
    enabled = getattr(ov, "_lane_arena_enabled", None)
    if arena is None or enabled is None or not enabled():
        return
    rows = np.zeros((128, 8), np.uint32)
    try:
        staged = [arena.stage("preflight", rows) for _ in range(stagers)]
        lost = sum(1 for buf in staged if buf.is_deleted())
    except (TypeError, AttributeError):
        return  # another arena than the one this probe knows: the run's own checks decide
    if lost:
        print(
            f"benchmark: the program cannot serve {stagers} concurrent "
            f"stagers of one shape: {lost} of {stagers} staged buffers were "
            "donated before their launch (ops/verify LaneArena); the cell "
            "is not measured on this program", file=sys.stderr)
        raise SystemExit(EXIT_CANNOT_SERVE)


def verdict_of(exc: BaseException):
    """The service's refusal as the reference words it."""
    if isinstance(exc, LightClientError) and _ROOT_MISMATCH in str(exc):
        return ("bad_root", None)
    return verdicts.of_exception(exc)


class Driver:
    def __init__(self, cell, seed: int, tracer):
        self.cell, self.seed, self.tracer = cell, seed, tracer
        self.cfg, self.mix = cell.config, cell.mix
        self.chain_id = self.cfg["chain_id"]
        self.marks = stats.Marks()
        self.service = None

    # -- set-up ----------------------------------------------------------

    def setup(self, seconds: float) -> None:
        cfg, mix = self.cfg, self.mix
        n, heights = cfg["validators"], cfg["chain_heights"]
        preflight_staging()
        t = time.monotonic()
        self.raw_vals = rawchain.make_validators(self.seed, "val", n)
        self.light = light_ref.lanes_counted(n, rawchain.VOTING_POWER, 2, 3)
        self.trust_level = tuple(cfg["trust_level"])
        self.trusting = light_ref.lanes_counted(
            n, rawchain.VOTING_POWER, *self.trust_level)
        vals = adapters.validator_set(self.raw_vals)
        self.chain = adapters.HeaderChain(self.chain_id, heights, vals,
                                          self.seed)
        t = self.marks.add(f"keys, validator set, {heights} headers", t)
        n_requests = int(mix["list_over_knee"] * mix["knee_replies_per_s"]
                         * seconds) + mix["workers"]
        self.requests, warm = self._plan(
            heights, n_requests, mix["warmup_requests"])
        touched = sorted({h for r in self.requests + warm
                          for h in (r["trust"], r["target"])})
        with rawchain.spawn_pool() as pool:
            self.commits = rawchain.sign_commits(
                self.raw_vals, self.chain_id,
                [self.chain.block_tuple(h) for h in touched], pool,
            )
        for r in self.requests + warm:
            if r["kind"] == "bad_target":
                self.commits[r["target"]] = rawchain.tamper(
                    self.commits[r["target"]], [r["lane"]], self.seed)
        t = self.marks.add(
            f"signing {len(touched)} commits in a pool "
            f"({len(self.requests)} requests)", t)
        self.now_ns = self.chain.now_ns()
        provider = adapters.ChainProvider(
            self.chain, self.commits, self.raw_vals.addresses)
        self.service = LightService(provider, self.chain_id,
                                    own_coalescer=True)
        self._warm_shapes(vals, mix["warm_buckets"])
        t = self.marks.add("key tables, verify shapes of every window size", t)
        self.service.start()
        self._play(warm, mix["workers"], time.monotonic() + 600, False)
        ov.WARM.wait_idle(600)
        self.marks.add(f"warm-up: {len(warm)} requests through the service", t)

    def _plan(self, heights: int, n_requests: int, n_warm: int):
        """Requests over disjoint pairs of heights. Every seed has the same
        counts of each kind; the seed moves heights, gaps, lanes and which
        requests are the bad ones."""
        mix = self.mix
        rng = random.Random(self.seed ^ 0xBACF11)
        lo, hi = mix["gap"]
        used: set[int] = set()
        pairs = []
        order = list(range(1, heights - lo + 1))
        rng.shuffle(order)
        want = n_requests + n_warm
        for trust in order:
            if len(pairs) == want:
                break
            if trust in used:
                continue
            for _ in range(8):
                target = trust + rng.randint(lo, hi)
                if target <= heights and target not in used:
                    used.update((trust, target))
                    pairs.append((trust, target))
                    break
        if len(pairs) < want:
            raise RuntimeError(
                f"a chain of {heights} heights holds {len(pairs)} disjoint "
                f"requests, {want} are needed")
        n_root = max(1, round(mix["bad_root_share"] * n_requests))
        n_target = max(1, round(mix["bad_target_share"] * n_requests))
        bad = rng.sample(range(n_requests), n_root + n_target)
        kinds = {i: "bad_root" for i in bad[:n_root]}
        kinds.update({i: "bad_target" for i in bad[n_root:]})
        # the warm-up plays one of each kind too
        kinds[n_requests] = "bad_root"
        kinds[n_requests + 1] = "bad_target"
        out = []
        for i, (trust, target) in enumerate(pairs):
            kind = kinds.get(i, "sound")
            req = {"trust": trust, "target": target, "kind": kind}
            if kind == "bad_target":
                req["lane"] = rng.randrange(self.light)
            elif kind == "bad_root":
                req["named"] = rawchain.seed_bytes(self.seed, "noroot", trust)
            out.append(req)
        return out[:n_requests], out[n_requests:]

    def _warm_shapes(self, vals, buckets) -> None:
        """The key tables of the set and an executable for every window
        size the coalescer can form (it compiles cold shapes in the
        background and serves from the host meanwhile; the window must see
        neither)."""
        crypto_batch.prestage_validators(vals)
        for b in buckets:
            ov.WARM.ready(("window", b))
        if not ov.WARM.wait_idle(900):
            raise RuntimeError("verify shapes did not finish compiling")
        if ov.WARM.failed:
            raise RuntimeError(f"verify shapes failed: {ov.WARM.failed}")

    def counters(self) -> dict:
        st = self.service.status()
        return {"cache": st["cache"], "service": st["requests"],
                "coalescer": st.get("coalescer", {})}

    # -- the measured window ---------------------------------------------

    def _ask(self, req: dict):
        try:
            got = self.service.verify_at_height(
                req["target"], trust_height=req["trust"],
                trust_hash=req.get("named"), now_ns=self.now_ns)
            return ("accept", bytes.fromhex(got["hash"]))
        except Exception as e:  # the answer is read, not assumed
            return verdict_of(e)

    def _play(self, requests, workers: int, t_end: float, traced: bool):
        """``workers`` threads share one cursor over ``requests``; each
        takes the next request when its last returned. Returns the answers
        (done, index, verdict) and whether the list ran out."""
        cursor = itertools.count()
        per_worker: list[list] = [[] for _ in range(workers)]
        ran_out = threading.Event()

        def work(answers: list) -> None:
            while time.monotonic() < t_end:
                i = next(cursor)
                if i >= len(requests):
                    ran_out.set()
                    return
                with tracing.span("request", traced):
                    verdict = self._ask(requests[i])
                answers.append((time.monotonic(), i, verdict))

        threads = [
            threading.Thread(target=work, name=f"bench-relayer-{k}",
                             args=(per_worker[k],), daemon=True)
            for k in range(workers)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=max(0.0, t_end - time.monotonic()) + 300)
        if any(th.is_alive() for th in threads):
            raise RuntimeError("a relayer worker did not stop")
        return sorted(a for lst in per_worker for a in lst), ran_out.is_set()

    def _lanes_needed(self, req: dict) -> int:
        """Lanes the checks of one request verify, by the plan: none for a
        root refused by its hash; the trusting check refuses a lane it
        counts before the target's own check runs."""
        if req["kind"] == "bad_root":
            return 0
        if req["kind"] == "bad_target" and req["lane"] < self.trusting:
            return self.light + self.trusting
        return 2 * self.light + self.trusting

    def run_window(self, seconds: float) -> dict:
        self.tracer.start()
        t0 = time.monotonic()
        t_end = t0 + seconds
        answers, ran_out = self._play(
            self.requests, self.mix["workers"], t_end, self.tracer.enabled)
        self.tracer.stop()
        inside = [a for a in answers if a[0] <= t_end]
        failed = sum(1 for a in inside if a[2][0] == "error")
        lanes = sum(self._lanes_needed(self.requests[a[1]])
                    for a in inside if a[2][0] != "error")
        gaps = [b[0] - a[0] for a, b in zip(inside, inside[1:])]
        return {
            "end_to_end": {"sigs_per_s": lanes / seconds},
            "attempted": len(inside),
            "failed": failed,
            "answers": answers,
            "ran_out": ran_out,
            "stats": {
                "replies_in_window": len(inside),
                "replies_all": len(answers),
                "replies_per_s": len(inside) / seconds,
                "requests_listed": len(self.requests),
                "reply_gap_ms_p50": stats.percentile(
                    [g * 1e3 for g in gaps], 50) if gaps else None,
            },
            "notes": {"errors": [a for a in answers if a[2][0] == "error"][:5]},
        }

    def close(self) -> None:
        if self.service is not None and self.service.is_running():
            self.service.stop()

    # -- correctness -----------------------------------------------------

    def check(self, window: dict, control: str, ctx) -> dict:
        """Every answer of the timed loop against the reference's verdict on
        the same request; the lanes the program's verifiers counted against
        the lanes the reference needed for those requests. With ``control``
        the control's verdicts stand in for the program's."""
        answers = window["answers"]
        pubkeys, addresses = self.raw_vals.pubkeys, self.raw_vals.addresses
        num, den = self.trust_level

        def jobs(verify_lanes):
            out = []
            for _t, i, _v in answers:
                req = self.requests[i]
                root = self.commits[req["trust"]]
                out.append((
                    root, self.commits[req["target"]],
                    req.get("named", root.block_hash), addresses, pubkeys,
                    rawchain.VOTING_POWER, num, den, verify_lanes,
                ))
            return out

        with rawchain.spawn_pool() as pool:
            want = list(pool.map(skipping_ref.request_job, jobs(None),
                                 chunksize=16))
            if control:
                stand_in = list(pool.map(
                    skipping_ref.request_job,
                    jobs(verdicts.CONTROLS[control]), chunksize=16))
        mismatches = refused_ok = lanes_needed = 0
        for k, (_t, _i, got) in enumerate(answers):
            if control:
                got = stand_in[k][0]
            if got != want[k][0]:
                mismatches += 1
            elif got[0] != "accept":
                refused_ok += 1
            lanes_needed += want[k][1]
        c = ctx.counters
        lanes_counted = sum(v for key, v in c.items() if key.startswith(SIGS))
        routed = {key[len(COALESCE_LANES):-1]: v for key, v in c.items()
                  if key.startswith(COALESCE_LANES)}
        notes = window.setdefault("notes", {})
        notes["refused_rightly"] = refused_ok
        notes["lanes_by_backend"] = {
            key[len(SIGS):-1]: v for key, v in c.items()
            if key.startswith(SIGS) and v}
        notes["coalesce_lanes_by_route"] = routed
        if sum(routed.values()):
            ctx.stats["device_lane_pct"] = (
                100.0 * routed.get('route="device"', 0) / sum(routed.values()))
        windows = c.get("coalescer.windows", 0)
        ctx.stats["coalescer"] = {
            k: c.get("coalescer." + k, 0)
            for k in ("windows", "device_windows", "tickets")}
        if windows:
            ctx.stats["lanes_per_window"] = lanes_counted / windows
        return {
            "verdict_mismatches": {"value": mismatches, "limit": 0},
            "lanes_counted_minus_needed": {
                "value": abs(lanes_counted - lanes_needed), "limit": 0},
            "cache_answers": {
                "value": c.get("cache.hits", 0) + c.get("cache.shared", 0),
                "limit": 0},
            "dispatch_faults": {
                "value": sum(v for key, v in c.items()
                             if key.startswith("faults.")),
                "limit": 0},
            "compiles_in_window": {
                "value": c.get("devstats.compiles", 0), "limit": 0},
            "service_shed": {
                "value": sum(c.get("service." + k, 0)
                             for k in ("rejected", "deadline", "stopped")),
                "limit": 0},
            "request_list_exhausted": {
                "value": int(window["ran_out"]), "limit": 0},
        }
