"""Closed-loop header replay: N syncing light clients, each a fresh
``light.Client`` (own MemStore, default commit verifier) walking the span of
signed heights one height at a time from its root, and starting over with a
new client at the end. Every step is the adjacent check: one
``verify_commit_light`` over the +2/3 prefix of the commit, through
types/validation -> crypto/batch -> ops/verify -> kernel -> bitmap readback.
No store or cache can answer: a fresh client holds nothing but its root.

Some heights have an altered commit that the provider serves first (a faulty
or hostile peer): the client must refuse it, naming the lane, and then
accepts the honest one. Lanes altered past the +2/3 cut must not be looked
at. What the program answered is compared, answer by answer, with the plain
reference's verdict on the same commit.
"""

from __future__ import annotations

import random
import threading
import time

from cometbft_tpu.light.client import Client, TrustOptions
from cometbft_tpu.light.store import MemStore

from ..harness import chain as rawchain
from ..harness import stats, tracing
from ..reference import light_ref
from . import adapters, verdicts

TPU_SIGS = 'prom.cometbft_tpu_crypto_verify_batch_sigs_total{backend="ed25519-tpu"}'


class _OneClientProvider(adapters.ChainProvider):
    """One client's view: the shared commits, plus the altered commit it is
    about to be served once."""

    def __init__(self, chain, commits, addresses):
        super().__init__(chain, commits, addresses)
        self.next_variant = None

    def light_block(self, height: int):
        raw, self.next_variant = self.next_variant, None
        if raw is not None and raw.height == height:
            return self.serve(raw)
        return super().light_block(height)


class Driver:
    def __init__(self, cell, seed: int, tracer):
        self.cell, self.seed, self.tracer = cell, seed, tracer
        self.cfg, self.mix = cell.config, cell.mix
        self.chain_id = self.cfg["chain_id"]
        self.marks = stats.Marks()

    # -- set-up ----------------------------------------------------------

    def setup(self, seconds: float) -> None:
        cfg, mix = self.cfg, self.mix
        n, span = cfg["validators"], cfg["span_heights"]
        t = time.monotonic()
        self.raw_vals = rawchain.make_validators(self.seed, "val", n)
        self.cut = light_ref.lanes_counted(n, rawchain.VOTING_POWER, 2, 3)
        vals = adapters.validator_set(self.raw_vals)
        self.chain = adapters.HeaderChain(self.chain_id, span, vals, self.seed)
        t = self.marks.add("keys, validator set, headers", t)
        with rawchain.spawn_pool() as pool:
            self.commits = rawchain.sign_commits(
                self.raw_vals, self.chain_id,
                [self.chain.block_tuple(h) for h in range(1, span + 1)], pool,
            )
        t = self.marks.add(f"signing {span} commits in a pool", t)
        self.variants = self._make_variants(span, n, mix["altered"])
        self.root = TrustOptions(
            period_ns=cfg["trusting_period_s"] * rawchain.SECOND_NS,
            height=1, hash=self.chain.block_ids[1].hash,
        )
        self.now_ns = self.chain.now_ns()
        self._warm(mix["warmup_heights"])
        self.marks.add("warm-up: first calls (tables, executables), a refusal", t)

    def _make_variants(self, span: int, n: int, altered: dict) -> dict:
        """Every seed alters the same number of heights in the same ways;
        the seed only moves them: which heights, which lanes, which bit."""
        rng = random.Random(self.seed ^ 0x51A7E)
        kinds = (
            ["in_cut"] * altered["in_cut"] + ["two_in_cut"] * altered["two_in_cut"]
            + ["past_cut"] * altered["past_cut"]
        )
        heights = rng.sample(range(2, span + 1), len(kinds))
        out = {}
        for h, kind in zip(heights, kinds):
            if kind == "in_cut":
                lanes = [rng.randrange(self.cut)]
            elif kind == "two_in_cut":
                lanes = rng.sample(range(self.cut), 2)
            else:
                lanes = [rng.randrange(self.cut, n)]
            out[h] = rawchain.tamper(self.commits[h], lanes, self.seed)
        return out

    def _new_client(self, provider) -> Client:
        return Client(
            chain_id=self.chain_id, trust_options=self.root, primary=provider,
            trusted_store=MemStore(),
        )

    def _provider(self):
        return _OneClientProvider(
            self.chain, self.commits, self.raw_vals.addresses
        )

    def _warm(self, heights: int) -> None:
        """The window's own call at the window's own shapes: root, a few
        adjacent steps, one refused commit."""
        prov = self._provider()
        client = self._new_client(prov)
        for h in range(2, 2 + heights):
            client.verify_light_block_at_height(h, self.now_ns)
        # one refusal: the first commit altered inside the cut, offered to
        # a new client that holds the honest height before it (stored, not
        # verified again)
        in_cut = [h for h, raw in self.variants.items()
                  if raw.tampered[0] < self.cut]
        if not in_cut:
            return  # a mix without altered commits has no refusal to warm
        h_bad = min(in_cut)
        prov = self._provider()
        client = self._new_client(prov)
        if h_bad > 2:
            client.trusted_store.save_light_block(prov.light_block(h_bad - 1))
        prov.next_variant = self.variants[h_bad]
        try:
            client.verify_light_block_at_height(h_bad, self.now_ns)
        except Exception as e:  # the refusal is the expected outcome
            if verdicts.of_exception(e)[0] == "error":
                raise

    def counters(self) -> dict:
        return {}

    # -- the measured window ---------------------------------------------

    def _steps(self) -> list:
        """One pass over the span: (height, variant, altered commit or None,
        whether the client then holds the height). An in-cut alteration is
        refused and the honest commit follows; one past the cut is accepted
        as it stands, so the honest commit is not asked for again (it would
        come from the client's store, not the verifier)."""
        steps = [(1, "honest", None)]
        for h in range(2, self.cfg["span_heights"] + 1):
            raw = self.variants.get(h)
            if raw is not None:
                steps.append((h, "altered", raw))
                if raw.tampered[0] >= self.cut:
                    continue
            steps.append((h, "honest", None))
        return steps

    def _walk(self, answers: list, t_end: float, traced: bool) -> None:
        steps = self._steps()
        while True:
            prov = self._provider()
            client = None
            for h, variant, raw in steps:
                try:
                    with tracing.span("verify_header", traced):
                        if h == 1:
                            client = self._new_client(prov)
                        else:
                            prov.next_variant = raw
                            client.verify_light_block_at_height(h, self.now_ns)
                    verdict = ("accept", None)
                except Exception as e:  # the answer is read, not assumed
                    verdict = verdicts.of_exception(e)
                done = time.monotonic()
                answers.append((done, h, variant, verdict))
                if done >= t_end:
                    return
                refused = verdict[0] != "accept"
                meant_refusal = raw is not None and raw.tampered[0] < self.cut
                if refused != meant_refusal:
                    break  # this client lost its place: start a new one

    def run_window(self, seconds: float) -> dict:
        n_clients = self.mix["clients"]
        per_client: list[list] = [[] for _ in range(n_clients)]
        self.tracer.start()
        t0 = time.monotonic()
        t_end = t0 + seconds
        threads = [
            threading.Thread(
                target=self._walk, name=f"bench-client-{i}",
                args=(per_client[i], t_end, self.tracer.enabled), daemon=True,
            )
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=seconds + 300)
        if any(t.is_alive() for t in threads):
            raise RuntimeError("a replay client did not stop")
        self.tracer.stop()
        answers = sorted(a for lst in per_client for a in lst)
        inside = [a for a in answers if a[0] <= t_end]
        failed = sum(1 for a in inside if a[3][0] == "error")
        rate = self.cut * (len(inside) - failed) / seconds
        return {
            "end_to_end": {"sigs_per_s": rate},
            "attempted": len(inside),
            "failed": failed,
            "answers": answers,
            "stats": {
                "headers_in_window": len(inside),
                "headers_all": len(answers),
                "headers_per_s": len(inside) / seconds,
                "header_ms_p50": stats.percentile(
                    [(b[0] - a[0]) * 1e3 for lst in per_client
                     for a, b in zip(lst, lst[1:])], 50),
            },
            "notes": {"errors": [a for a in answers if a[3][0] == "error"][:5]},
        }

    def close(self) -> None:
        """Nothing to stop: the clients ended with the window, and the
        device arena is the program's own cache."""

    # -- correctness -----------------------------------------------------

    def check(self, window: dict, control: str, ctx) -> dict:
        """Every answer the timed loop got, against the reference's verdict
        on the same commit (each distinct commit is judged once). With
        ``control`` the control's verdicts stand in for the program's."""
        answers = window["answers"]
        distinct = sorted({(h, v) for _t, h, v, _ in answers})
        raw_of = lambda h, v: (  # noqa: E731
            self.variants[h] if v == "altered" else self.commits[h])
        jobs = [(raw_of(h, v), self.raw_vals.pubkeys, rawchain.VOTING_POWER, "")
                for h, v in distinct]
        with rawchain.spawn_pool() as pool:
            want = dict(zip(distinct, pool.map(verdicts.reference_job, jobs)))
            if control:
                cjobs = [j[:3] + (control,) for j in jobs]
                stand_in = dict(zip(
                    distinct, pool.map(verdicts.reference_job, cjobs)))
        mismatches = 0
        refused_ok = 0
        for _t, h, v, got in answers:
            if control:
                got = stand_in[(h, v)]
            if got != want[(h, v)]:
                mismatches += 1
            elif got[0] == "reject":
                refused_ok += 1
        lanes = ctx.counters.get(TPU_SIGS, 0)
        n_ok = sum(1 for a in answers if a[3][0] != "error")
        window.setdefault("notes", {})["refused_rightly"] = refused_ok
        return {
            "verdict_mismatches": {"value": mismatches, "limit": 0},
            "lanes_not_through_verifier": {
                "value": abs(n_ok * self.cut - lanes), "limit": 0},
            "compiles_in_window": {
                "value": ctx.counters.get("devstats.compiles", 0), "limit": 0},
        }
