"""Closed-loop bisection across a rotating validator set: one light client
(a relayer updating a client after a gap, a light proxy, state sync's
light-client state provider), a fresh ``light.Client`` each pass (own
MemStore, default commit verifier) that trusts the root height and asks for
the target height with skipping verification, through light/client.py
bisection -> light/verifier.verify_non_adjacent -> types/validation (the
trusting check by address, then the light check) -> crypto/batch ->
ops/verify (key arena, kernel). The set rotates under the chain, so a skip
reaches only so far and the client pivots (9/10) until the trusted set
still signs; every verified step meets keys the device arena has not held.

What the provider serves follows one script a pass, made in set-up by the
plain bisection of reference/bisect_ref over the rotation's arithmetic:
some steps are first served a commit with one counted lane altered (inside
the trusting check's lanes, inside the light check's, or past both cuts).
A refused request is asked again and the client resumes from its last
trusted pivot, as a relayer retrying another peer would. Every answered
request (the heights the client came to trust, and its accept or refusal)
is compared with the reference's answer on the same script, and the lanes
the verifiers counted with the lanes the reference needed.
"""

from __future__ import annotations

import gc
import random
import re
import time

from cometbft_tpu.light.client import Client, TrustOptions
from cometbft_tpu.light.errors import (
    InvalidHeaderError, LightBlockNotFoundError, VerificationFailedError,
)
from cometbft_tpu.light.provider import Provider
from cometbft_tpu.light.store import MemStore
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig, Header, PartSetHeader,
    Version,
)
from cometbft_tpu.types.light_block import LightBlock, SignedHeader
from cometbft_tpu.types.validation import (
    NotEnoughVotingPowerError, VerificationError,
)
from cometbft_tpu.types.validator_set import ValidatorSet

from ..harness import chain as rawchain
from ..harness import rotation, stats, tracing
from ..reference import bisect_ref, light_ref
from . import adapters

SIGS = "prom.cometbft_tpu_crypto_verify_batch_sigs_total{"
SOUND = bisect_ref.SOUND
_LANE = re.compile(r"wrong signature \(#(\d+)\)")
EXIT_CANNOT_RUN = 5


def preflight() -> None:
    """This deployment needs a validator set that finds a validator by
    address without a scan (the trusting check looks up each of 10,000
    signatures: a scan is ~10^8 comparisons an attempt, so no verified
    step fits a window), and a count of bisection attempts by outcome,
    the steps every per-step metric of the cell is read against. A program
    without them cannot run the cell: said here, before any set-up, by
    exit code 5 and no result line."""
    import sys

    from cometbft_tpu.libs import metrics as libmetrics

    missing = [what for what, ok in (
        ("types.ValidatorSet.address_index",
         hasattr(ValidatorSet, "address_index")),
        ("light_bisection_attempts_total", hasattr(
            libmetrics.node_metrics(), "light_bisection_attempts_total")),
    ) if not ok]
    if missing:
        print(f"benchmark: the program lacks {missing}: the cell is not "
              "measured on this program", file=sys.stderr)
        raise SystemExit(EXIT_CANNOT_RUN)


class _WindowOver(Exception):
    """The provider's answer once the window has closed: the request in
    flight ends there and is no answer."""


def refusal_of(exc: BaseException):
    """The program's refusal as the reference words it: ("reject", height,
    check, lane), ("power", height, check), or ("error", text) for anything
    that is not a refusal of a commit."""
    height, check = None, "trusting"
    seen, e = set(), exc
    verdict = None
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        if isinstance(e, VerificationFailedError):
            height = e.to_height
        if isinstance(e, InvalidHeaderError):
            check = "light"
        if verdict is None and isinstance(e, NotEnoughVotingPowerError):
            verdict = ("power",)
        elif verdict is None and isinstance(e, VerificationError):
            m = _LANE.search(str(e))
            if m:
                verdict = ("reject", int(m.group(1)))
        e = getattr(e, "reason", None) or e.__cause__
    if verdict is None or height is None:
        return ("error", f"{type(exc).__name__}: {exc}"[:200])
    if verdict[0] == "power":
        return ("power", height, check)
    return ("reject", height, check, verdict[1])


class _RecordingStore(MemStore):
    """The client's trusted store, which also writes down when each height
    came to be trusted: the steps of the pass, on the client's clock."""

    def __init__(self, steps: list):
        super().__init__()
        self._steps = steps

    def save_light_block(self, lb) -> None:
        super().save_light_block(lb)
        self._steps.append((time.monotonic(), "save", lb.height))


class _ScriptProvider(Provider):
    """One pass's provider: fetch ``i`` gets the script's entry ``i`` when
    the heights agree, else the sound commit (bisect_ref.script_fetch's
    rule); a fetch that is not the script's is counted. After ``t_end`` it
    answers nothing."""

    def __init__(self, driver, t_end: float):
        self._d = driver
        self._t_end = t_end
        self._cursor = 0
        self.off_script = 0

    def chain_id(self) -> str:
        return self._d.chain_id

    def light_block(self, height: int):
        if time.monotonic() >= self._t_end:
            raise _WindowOver()
        script, i = self._d.script, self._cursor
        self._cursor += 1
        if i < len(script) and script[i][0] == height:
            variant = script[i][1]
        else:
            self.off_script += 1
            variant = SOUND
        raw = self._d.commits.get((height, variant))
        if raw is None:
            raise LightBlockNotFoundError(height)
        return self._d.light_block(raw)

    def report_evidence(self, ev) -> None:
        pass


class Driver:
    def __init__(self, cell, seed: int, tracer):
        self.cell, self.seed, self.tracer = cell, seed, tracer
        self.cfg, self.mix = cell.config, cell.mix
        self.chain_id = self.cfg["chain_id"]
        self.marks = stats.Marks()
        self.off_script = 0
        self.untimed_passes: list = []
        self.untimed_off_script = 0

    # -- set-up ----------------------------------------------------------

    def setup(self, seconds: float) -> None:
        preflight()
        cfg, mix = self.cfg, self.mix
        n = cfg["validators"]
        self.root, self.target = cfg["root_height"], cfg["target_height"]
        self.trust = tuple(cfg["trust_level"])
        self.light = light_ref.lanes_counted(n, rawchain.VOTING_POWER, 2, 3)
        self.trusting = light_ref.lanes_counted(
            n, rawchain.VOTING_POWER, *self.trust)
        t = time.monotonic()
        # the pass's script and the steps a sound client takes over it:
        # the plain bisection over the rotation's arithmetic, before any
        # key exists (the altered steps' detours included)
        self.script, self.plan_events, alterations = self._plan(
            mix["altered"])
        heights = sorted({h for h, _ in self.script})
        t = self.marks.add(f"the pass's script: {len(heights)} heights", t)
        with rawchain.spawn_pool() as pool:
            self.sets = rotation.RotatingSets(
                self.seed, "val", n, cfg["rotate_per_height"],
                heights + [h + 1 for h in heights], pool)
            t = self.marks.add(
                f"keys of {len(heights)} rotating sets and their next", t)
            self._headers(heights)
            t = self.marks.add("validator sets, their hashes, headers", t)
            self.commits = {
                (h, SOUND): raw for h, raw in rotation.sign_commits(
                    self.sets, self.chain_id,
                    [self._block_tuple(h) for h in heights],
                    {h: self._stamps(h) for h in heights}, pool,
                ).items()
            }
        t = self.marks.add(f"signing {len(heights)} commits in a pool", t)
        self.altered = self._alter(alterations)
        self.trust_options = TrustOptions(
            period_ns=cfg["trusting_period_s"] * rawchain.SECOND_NS,
            height=self.root, hash=self.headers[self.root].hash(),
        )
        self.now_ns = (rawchain.BASE_TIME_NS + (self.target + 2)
                       * cfg["block_time_s"] * rawchain.SECOND_NS)
        self._untimed_pass()
        t = self.marks.add(
            "a whole pass from an empty key arena: compiles the builder "
            "and kernel shapes it meets", t)
        self._untimed_pass()
        t = self.marks.add(
            "a whole pass from the arena a pass leaves: the window's own "
            "pass, its shapes and builds", t)
        # What set-up made is set aside from the collector: the chain's
        # sets, headers and commits (a full node's data, which lives in
        # another process) and the program's compiled state. Frozen
        # before the passes, the compiles' millions of objects were the
        # young heap, and the window's full collections walked them:
        # ~4.5 s of a 30 s window in a fresh process against ~1.5 s in
        # one that had frozen them (TPU v5e host, 10,000 validators).
        gc.collect()
        gc.freeze()
        self.marks.add("set-up's objects set aside from the collector", t)

    def _untimed_pass(self) -> None:
        """One whole pass of the script before the window; its answers are
        judged with the window's. The key arena's lookups keep the keys a
        pass touched last,
        so a pass that follows a whole pass builds the same tables at the
        same steps as every other such pass: the second of these is the
        window's pass, and compiles what the first did not meet (a first
        pass meets an empty arena, where the window meets the keys of the
        pass before and the slot vectors it kept)."""
        steps: list = []
        answers: list = []
        prov = _ScriptProvider(self, float("inf"))
        self._one_pass(prov, steps, answers,
                       self.mix["max_requests_per_pass"])
        self.untimed_passes.append({"steps": steps, "answers": answers})
        self.untimed_off_script += prov.off_script

    def _stamps(self, h: int) -> list[int]:
        base = self._time_ns(h)
        return [base + 1_000 * i for i in range(self.cfg["validators"])]

    def _time_ns(self, h: int) -> int:
        return (rawchain.BASE_TIME_NS
                + h * self.cfg["block_time_s"] * rawchain.SECOND_NS)

    def _headers(self, heights) -> None:
        """One ValidatorSet object a height (the same on every pass), and
        its header. The header's hashes other than the two validator-set
        hashes are seeded constants; ``next_validators_hash`` is the next
        height's set hashed from its leaves alone (reference/rfc6962)."""
        fill = lambda tag: rawchain.seed_bytes(self.seed, "hdr", tag)  # noqa: E731
        psh_hash = fill("psh")
        self.vals: dict[int, ValidatorSet] = {}
        self.headers: dict[int, Header] = {}
        self.block_ids: dict[int, BlockID] = {}
        for h in heights:
            raw = self.sets.sets[h]
            vals = adapters.validator_set(raw)
            own = vals.hash()
            if own != self.sets.root(h):
                raise RuntimeError(
                    f"the program hashes the set of height {h} otherwise "
                    "than its leaves' RFC 6962 tree")
            hdr = Header(
                version=Version(block=11, app=1),
                chain_id=self.chain_id,
                height=h,
                time_ns=self._time_ns(h),
                last_block_id=BlockID(
                    hash=fill(("last", h)),
                    part_set_header=PartSetHeader(total=1, hash=psh_hash)),
                last_commit_hash=fill("lc"),
                data_hash=fill("data"),
                validators_hash=own,
                next_validators_hash=self.sets.root(h + 1),
                consensus_hash=fill("cons"),
                app_hash=fill("app"),
                last_results_hash=fill("res"),
                evidence_hash=fill("ev"),
                proposer_address=vals.validators[h % len(vals)].address,
            )
            self.vals[h] = vals
            self.headers[h] = hdr
            self.block_ids[h] = BlockID(
                hash=hdr.hash(),
                part_set_header=PartSetHeader(
                    total=adapters.PSH_TOTAL, hash=psh_hash))

    def _block_tuple(self, h: int):
        bid = self.block_ids[h]
        return (h, bid.hash, bid.part_set_header.total,
                bid.part_set_header.hash)

    def light_block(self, raw: rawchain.RawCommit) -> LightBlock:
        """A fresh Commit object each fetch, as a provider that decodes a
        reply hands out; the height's one ValidatorSet object."""
        addresses = self.sets.sets[raw.height].addresses
        commit = Commit(
            height=raw.height, round=raw.round,
            block_id=self.block_ids[raw.height],
            signatures=[
                CommitSig(BLOCK_ID_FLAG_COMMIT, addr, ts, sig)
                for addr, ts, sig in zip(
                    addresses, raw.timestamps, raw.signatures)
            ],
        )
        return LightBlock(
            signed_header=SignedHeader(
                header=self.headers[raw.height], commit=commit),
            validator_set=self.vals[raw.height],
        )

    # -- the pass's script -------------------------------------------------

    def _trusts(self, trusted: int, h: int) -> bool:
        """Every validator signs at equal power: the trusting check passes
        when the sets share more than trust_level of the trusted power."""
        num, den = self.trust
        n, power = self.cfg["validators"], rawchain.VOTING_POWER
        return (self.sets_overlap(trusted, h) * power
                > n * power * num // den)

    def sets_overlap(self, a: int, b: int) -> int:
        rotate, n = self.cfg["rotate_per_height"], self.cfg["validators"]
        return max(0, n - rotate * abs(b - a))

    def _trusting_lanes(self, trusted: int, h: int) -> list[int]:
        """The commit lanes of ``h`` the trusting check from ``trusted``
        counts: signers of the trusted set, in commit order, until the
        tally passes."""
        known = set(self.sets.sets[trusted].addresses)
        out = [lane for lane, a in enumerate(self.sets.sets[h].addresses)
               if a in known]
        return out[:self.trusting]

    def _plan(self, altered: list):
        """The script of a pass, the steps a sound client takes over it
        (kind, height, lanes each verifies) and the attempts that meet an
        altered commit. ``altered`` is [kind, share] pairs: the attempt
        that passes the trust level at that share of an unaltered pass's
        such attempts meets the altered commit of that kind. The steps are
        the same for every seed, so every seed's pass bisects the same
        heights and does the same work; the seed moves which lane and bit
        (:meth:`_alter`) and the keys."""
        trustable = len(self._walk_plan({})[2])
        picks = {round(share * trustable): kind for kind, share in altered}
        script, events, done, answers = self._walk_plan(picks)
        if (len(picks) != len(altered) or len(done) != len(picks)
                or answers[-1][1][0] != "accept"):
            raise RuntimeError(f"no plan alters the steps {altered} asks for")
        return script, events, done

    def _walk_plan(self, picks: dict):
        """One pass by arithmetic: every validator signs, so an attempt
        passes the trusting check when the sets share enough power, and
        the ``k``-th such attempt meets the altered commit ``picks[k]``
        asks for (``trusting``: refused in the trusting check, ``light``:
        refused in the light check, ``past``: past both cuts, accepted).
        Returns (script, steps, the altered attempts (k, kind, trusted,
        height), answers)."""
        script: list = []
        done: list = []
        trustable = [0]
        both = self.trusting + self.light

        def fetch(h):
            script.append((h, SOUND))
            return SOUND, len(script) - 1

        def judge(trusted, h, _variant, at):
            if trusted is None or h == trusted + 1:
                return ("verified", self.light)
            if not self._trusts(trusted, h):
                return ("cant_trust", 0)
            k = trustable[0]
            trustable[0] += 1
            kind = picks.get(k)
            if kind is None:
                if not picks:
                    done.append((k, None, trusted, h))
                return ("verified", both)
            script[at] = (h, f"alt{k}")
            done.append((k, kind, trusted, h))
            if kind == "trusting":
                return ("reject", "trusting", -1, self.trusting)
            if kind == "light":
                return ("reject", "light", -1, both)
            return ("verified", both)

        answers = bisect_ref.walk(
            fetch, judge, self.root, self.target,
            self.mix["max_requests_per_pass"], lambda h, v: b"")
        return script, _flat_events(answers), done, answers

    def _alter(self, alterations) -> list:
        """The altered commits of the script: one seeded bit of one lane
        of the kind each alteration asks for."""
        rng = random.Random(self.seed ^ 0x1A4E5)
        n = self.cfg["validators"]
        out = []
        for k, kind, trusted, h in alterations:
            counted = self._trusting_lanes(trusted, h)
            taken = set(counted)
            choices = {
                "trusting": counted,
                "light": [i for i in range(self.light) if i not in taken],
                "past": [i for i in range(self.light, n) if i not in taken],
            }[kind]
            if not choices:
                raise RuntimeError(
                    f"no lane of {h}'s commit is {kind} from {trusted}")
            lane = rng.choice(choices)
            self.commits[(h, f"alt{k}")] = rawchain.tamper(
                self.commits[(h, SOUND)], [lane], self.seed ^ k)
            out.append((k, kind, trusted, h, lane))
        return out

    # -- the client ------------------------------------------------------

    def _new_client(self, provider, steps: list) -> Client:
        return Client(
            chain_id=self.chain_id, trust_options=self.trust_options,
            primary=provider, trusted_store=_RecordingStore(steps),
        )

    def counters(self) -> dict:
        return {"script": {"off": self.off_script}}

    # -- the measured window ---------------------------------------------

    def _client_loop(self, passes: list, t_end: float, traced: bool) -> None:
        """The one client, pass after pass, until the window closes."""
        max_requests = self.mix["max_requests_per_pass"]
        while time.monotonic() < t_end:
            steps: list = []
            answers: list = []
            passes.append({"steps": steps, "answers": answers})
            prov = _ScriptProvider(self, t_end)
            try:
                with tracing.span("pass", traced):
                    self._one_pass(prov, steps, answers, max_requests)
            except _WindowOver:
                pass
            finally:
                self.off_script += prov.off_script

    def _one_pass(self, prov, steps, answers, max_requests) -> None:
        try:
            client = self._new_client(prov, steps)
            verdict = ("accept", client.trusted_light_block(self.root).hash())
        except _WindowOver:
            raise
        except Exception as e:  # the answer is read, not assumed
            verdict = _refused(e, steps)
            answers.append((time.monotonic(), (), verdict))
            return
        answers.append((time.monotonic(), (self.root,), verdict))
        for _ in range(max_requests):
            before = len(steps)
            try:
                lb = client.verify_light_block_at_height(
                    self.target, self.now_ns)
                verdict = ("accept", lb.hash())
            except _WindowOver:
                raise
            except Exception as e:  # the answer is read, not assumed
                verdict = _refused(e, steps)
            trace = tuple(h for _t, kind, h in steps[before:] if kind == "save")
            answers.append((time.monotonic(), trace, verdict))
            if verdict[0] in ("accept", "error"):
                return

    def run_window(self, seconds: float) -> dict:
        passes: list = []
        pauses = _CollectorPauses()
        self.tracer.start()
        t0 = time.monotonic()
        t_end = t0 + seconds
        with pauses:
            self._client_loop(passes, t_end, self.tracer.enabled)
        self.tracer.stop()
        plan = self.plan_events
        lanes = failed = steps_in = 0
        for p in passes:
            for k, (t, kind, h) in enumerate(p["steps"]):
                if t <= t_end:
                    steps_in += 1
                    if k < len(plan) and plan[k][:2] == (kind, h):
                        lanes += plan[k][2]
            errors = sum(1 for t, _tr, v in p["answers"]
                         if t <= t_end and v[0] == "error")
            failed += errors
        attempted = steps_in + failed
        done = [p for p in passes if p["answers"]
                and p["answers"][-1][2][0] == "accept"
                and p["answers"][-1][0] <= t_end]
        return {
            "end_to_end": {"sigs_per_s": lanes / seconds},
            "attempted": attempted,
            "failed": failed,
            "passes": passes,
            "stats": {
                "steps_in_window": steps_in,
                "steps_per_s": steps_in / seconds,
                "passes_started": len(passes),
                "passes_done_in_window": len(done),
                "pass_s_p50": stats.percentile(
                    [p["answers"][-1][0] - p["answers"][0][0] for p in done],
                    50),
                "altered": [list(a) for a in self.altered],
                "gc_collections": pauses.collections,
                "gc_pause_s": pauses.seconds,
            },
            "notes": {"errors": [
                a[2] for p in passes for a in p["answers"]
                if a[2][0] == "error"][:5]},
        }

    def close(self) -> None:
        """Nothing to stop: the client ended with the window, and the
        device arena is the program's own cache."""

    # -- correctness -----------------------------------------------------

    def reference_chain(self) -> bisect_ref.Chain:
        sets = {h: (s.addresses, s.pubkeys)
                for h, s in self.sets.sets.items()}
        return bisect_ref.Chain(sets, dict(self.commits),
                                rawchain.VOTING_POWER, self.trust)

    def check(self, window: dict, control: str, ctx) -> dict:
        """Every answer of the timed loop and of the set-up's passes against
        the reference's answer at the same place of its pass; the lanes the
        program's verifiers counted in the window against the lanes the
        reference needed for the steps the client took there. With
        ``control`` the control's answers stand in for the program's."""
        chain = self.reference_chain()
        with rawchain.spawn_pool() as pool:
            memo = bisect_ref.verified_lanes(chain, pool)
        verify = bisect_ref.memo_verifier(memo)
        max_requests = self.mix["max_requests_per_pass"]
        want = bisect_ref.pass_answers(
            chain, self.script, self.root, self.target, max_requests, verify)
        stand_in = None
        if control:
            stand_in = bisect_ref.pass_answers(
                chain, self.script, self.root, self.target, max_requests,
                bisect_ref.control_verifier(control, verify),
                by_index=control == "by_index")
        want_events = _flat_events(want)
        mismatches = answered = refused_ok = 0
        lanes_needed = 0
        for p in window["passes"] + self.untimed_passes:
            for k, (_t, trace, verdict) in enumerate(p["answers"]):
                answered += 1
                got = (trace, verdict)
                if stand_in is not None:
                    got = stand_in[k][:2] if k < len(stand_in) else None
                expect = want[k][:2] if k < len(want) else None
                if got != expect:
                    mismatches += 1
                elif verdict[0] == "reject":
                    refused_ok += 1
        for p in window["passes"]:
            for k, (_t, kind, h) in enumerate(p["steps"]):
                if k < len(want_events) and want_events[k][:2] == (kind, h):
                    lanes_needed += want_events[k][2]
        c = ctx.counters
        lanes_counted = sum(v for key, v in c.items() if key.startswith(SIGS))
        notes = window.setdefault("notes", {})
        notes["refused_rightly"] = refused_ok
        notes["answers"] = answered
        notes["lanes_by_backend"] = {
            key[len(SIGS):-1]: v for key, v in c.items()
            if key.startswith(SIGS) and v}
        return {
            "verdict_mismatches": {"value": mismatches, "limit": 0},
            "lanes_counted_minus_needed": {
                "value": abs(lanes_counted - lanes_needed), "limit": 0},
            "plan_steps_off_reference": {
                "value": _events_off(self.plan_events, want_events),
                "limit": 0},
            "fetches_off_script": {
                "value": c.get("script.off", 0) + self.untimed_off_script,
                "limit": 0},
            "dispatch_faults": {
                "value": sum(v for key, v in c.items()
                             if key.startswith("faults.")),
                "limit": 0},
            "compiles_in_window": {
                "value": c.get("devstats.compiles", 0), "limit": 0},
        }


class _CollectorPauses:
    """The collector's runs while the window is open: how many of each
    generation, and the seconds they held the interpreter."""

    def __init__(self):
        self.collections = [0, 0, 0]
        self.seconds = 0.0
        self._t0 = 0.0

    def _note(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t0
            self.collections[info["generation"]] += 1

    def __enter__(self):
        gc.callbacks.append(self._note)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._note)


def _refused(exc: BaseException, steps: list):
    verdict = refusal_of(exc)
    if verdict[0] != "error":
        steps.append((time.monotonic(), "refuse", verdict[1]))
    return verdict


def _flat_events(answers) -> list[tuple]:
    """The steps of a pass in order: ("save", height, lanes) for each height
    the client came to trust, ("refuse", height, lanes) for each refusal."""
    out = []
    for trace, verdict, lanes in answers:
        for h, n in zip(trace, lanes):
            out.append(("save", h, n))
        if verdict[0] in ("reject", "power"):
            out.append(("refuse", verdict[1], lanes[len(trace)]))
    return out


def _events_off(a: list, b: list) -> int:
    return sum(1 for x, y in zip(a, b) if x != y) + abs(len(a) - len(b))
