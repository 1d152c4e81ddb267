"""Guarded-field lockset inference (devtools/lint/graph/fields):
synthetic guard-inference fixtures for CLNT011/012, the ``# lockfree:``
marker and suppression contracts, the fieldguards.json artifact, the
libs/sync lockset sanitizer (record/enforce), the ``--changed``
incremental CLI mode, and the engine-wide gates (zero unbaselined
CLNT011/012; shipped fieldguards.json in sync with the tree and with
lockorder.json's lock registry).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from cometbft_tpu.devtools.lint import (
    ALL_CHECKERS,
    apply_baseline,
    lint_root,
    load_baseline,
)
from cometbft_tpu.devtools.lint.__main__ import main as lint_main
from cometbft_tpu.devtools.lint.engine import parse_root
from cometbft_tpu.devtools.lint.graph import (
    FIELD_RULES,
    analyze_contexts,
    analyze_fields,
)
from cometbft_tpu.libs import sync as libsync

pytestmark = pytest.mark.quick

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "cometbft_tpu")
SHIPPED_FIELDS = os.path.join(
    PKG, "devtools", "lint", "graph", "fieldguards.json"
)
SHIPPED_GRAPH = os.path.join(
    PKG, "devtools", "lint", "graph", "lockorder.json"
)

# a minimal libs/sync stand-in so fixture trees look like the engine
SYNC_STUB = """
import threading
def Mutex(name=""):
    return threading.Lock()
def RLock(name=""):
    return threading.RLock()
def Condition(lock=None, name=""):
    return threading.Condition(lock)
"""


def run_fields(tmp_path, files: dict[str, str]):
    files = dict(files)
    files.setdefault("libs/sync.py", SYNC_STUB)
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    contexts, errors = parse_root(str(tmp_path))
    assert not errors, errors
    return analyze_fields(analyze_contexts(contexts))


def codes(findings):
    return sorted(f.code for f in findings)


# ------------------------------------------------------- guard inference


class TestGuardInference:
    GUARDED = {
        "switch.py": """
        import threading
        from .libs import sync as libsync

        class Switch:
            def __init__(self):
                self._mtx = libsync.Mutex("fix.peers")
                self.peers = {}
                self._thr = threading.Thread(target=self._run, daemon=True)

            def _run(self):
                with self._mtx:
                    self.peers["a"] = 1

            def snapshot(self):
                with self._mtx:
                    return dict(self.peers)
        """
    }

    def test_consistently_guarded_field_is_clean(self, tmp_path):
        fields = run_fields(tmp_path, self.GUARDED)
        assert fields.findings() == [
        ], [f.render() for f in fields.findings()]
        info = fields.fields[("Switch", "peers")]
        assert info.guard == frozenset({"fix.peers"})
        # the init write is excluded from the guard meet but kept as a
        # site; the thread root and the main-thread reader both count
        assert len(info.threads) >= 2

    def test_lock_free_read_is_clnt011(self, tmp_path):
        files = dict(self.GUARDED)
        files["switch.py"] = files["switch.py"].replace(
            "with self._mtx:\n                    return dict(self.peers)",
            "return dict(self.peers)",
        )
        fields = run_fields(tmp_path, files)
        fs = fields.findings()
        assert codes(fs) == ["CLNT011"], [f.render() for f in fs]
        assert "Switch.peers" in fs[0].message
        assert "fix.peers" in fs[0].message
        assert fs[0].path == "switch.py"

    CLNT012 = {
        "switch.py": """
        import threading

        class Switch:
            def __init__(self):
                self.peers = {}
                self._t1 = threading.Thread(target=self._run_a, daemon=True)
                self._t2 = threading.Thread(target=self._run_b, daemon=True)

            def _run_a(self):
                self.peers["a"] = 1

            def _run_b(self):
                self.peers["b"] = 2
        """
    }

    def test_guardless_multi_writer_is_clnt012(self, tmp_path):
        fields = run_fields(tmp_path, self.CLNT012)
        fs = fields.findings()
        assert codes(fs) == ["CLNT012"], [f.render() for f in fs]
        assert "Switch.peers" in fs[0].message
        assert "multiple threads" in fs[0].message

    def test_single_writer_thread_is_not_clnt012(self, tmp_path):
        # one writer root, lock-free: no cross-thread write race exists
        files = {
            "switch.py": """
            import threading

            class Switch:
                def __init__(self):
                    self.peers = {}
                    self._t = threading.Thread(target=self._run, daemon=True)

                def _run(self):
                    self.peers["a"] = 1
            """
        }
        assert run_fields(tmp_path, files).findings() == []

    def test_helper_inherits_caller_context(self, tmp_path):
        # _remove holds no lock lexically, but EVERY caller holds the
        # update mutex — the meet-over-call-sites context keeps the
        # guard exact (this is the CListMempool._remove_tx_el shape)
        files = {
            "mempool.py": """
            import threading
            from .libs import sync as libsync

            class CListMempool:
                def __init__(self):
                    self._mtx = libsync.Mutex("fix.update")
                    self.tx_map = {}
                    self._thr = threading.Thread(
                        target=self._loop, daemon=True
                    )

                def _loop(self):
                    with self._mtx:
                        self._remove("k")

                def update(self):
                    with self._mtx:
                        self._remove("j")

                def _remove(self, key):
                    self.tx_map.pop(key, None)
            """
        }
        fields = run_fields(tmp_path, files)
        assert fields.findings() == [
        ], [f.render() for f in fields.findings()]
        assert fields.fields[("CListMempool", "tx_map")].guard == frozenset(
            {"fix.update"}
        )

    def test_contextmanager_helper_holds_its_lock_for_the_body(
        self, tmp_path
    ):
        # ``with self._region():`` — a @contextmanager method that yields
        # inside ``with self._mtx:`` — guards the caller's body like the
        # lock itself (the ConsensusState._fsm_region shape); a helper
        # that yields outside its lock guards nothing
        files = {
            "state.py": """
            import contextlib
            import threading
            from .libs import sync as libsync

            class Switch:
                def __init__(self):
                    self._mtx = libsync.RLock("fix.state")
                    self.peers = {}
                    self.seen = {}
                    self._thr = threading.Thread(
                        target=self._loop, daemon=True
                    )

                @contextlib.contextmanager
                def _region(self):
                    with self._mtx:
                        try:
                            yield
                        finally:
                            self.seen = dict(self.peers)

                @contextlib.contextmanager
                def _after(self):
                    with self._mtx:
                        pass
                    yield

                def _loop(self):
                    with self._region():
                        self.peers["a"] = 1

                def update(self):
                    with self._region():
                        self.peers["b"] = 2
            """
        }
        fields = run_fields(tmp_path, files)
        assert fields.findings() == [
        ], [f.render() for f in fields.findings()]
        guard = frozenset({"fix.state"})
        assert fields.fields[("Switch", "peers")].guard == guard
        assert fields.fields[("Switch", "seen")].guard == guard
        files["state.py"] = files["state.py"].replace(
            "with self._region():\n                        "
            'self.peers["b"]',
            "with self._after():\n                        "
            'self.peers["b"]',
        )
        fs = run_fields(tmp_path, files).findings()
        assert codes(fs) == ["CLNT012"], [f.render() for f in fs]

    def test_init_only_field_is_out_of_scope(self, tmp_path):
        # written once during construction, read everywhere: immutable
        # after publication, no guard needed
        files = {
            "switch.py": """
            import threading

            class Switch:
                def __init__(self):
                    self.peers = {}
                    self._t = threading.Thread(target=self._run, daemon=True)

                def _run(self):
                    return len(self.peers)
            """
        }
        fields = run_fields(tmp_path, files)
        assert fields.findings() == []
        assert ("Switch", "peers") not in fields.fields


# --------------------------------------------------- lockfree + suppression


class TestLockfreeMarker:
    def test_marker_on_write_site_exempts_field(self, tmp_path):
        files = {
            "switch.py": """
            import threading

            class Switch:
                def __init__(self):
                    self.peers = {}
                    self._t1 = threading.Thread(target=self._run_a, daemon=True)
                    self._t2 = threading.Thread(target=self._run_b, daemon=True)

                def _run_a(self):
                    # lockfree: idempotent interning, double store is benign
                    self.peers["a"] = 1

                def _run_b(self):
                    self.peers["b"] = 2
            """
        }
        fields = run_fields(tmp_path, files)
        assert fields.findings() == []
        info = fields.fields[("Switch", "peers")]
        assert info.lockfree == (
            "idempotent interning, double store is benign"
        )

    def test_marker_on_init_write_exempts_field(self, tmp_path):
        # the canonical placement: one marker above the constructor
        # assignment brands the whole field
        files = {
            "switch.py": """
            import threading

            class Switch:
                def __init__(self):
                    # lockfree: single-writer slot stores, GIL-atomic
                    self.peers = {}
                    self._t1 = threading.Thread(target=self._run_a, daemon=True)
                    self._t2 = threading.Thread(target=self._run_b, daemon=True)

                def _run_a(self):
                    self.peers["a"] = 1

                def _run_b(self):
                    self.peers["b"] = 2
            """
        }
        fields = run_fields(tmp_path, files)
        assert fields.findings() == []
        assert fields.fields[("Switch", "peers")].lockfree

    def test_bare_marker_without_reason_is_ignored(self, tmp_path):
        files = {
            "switch.py": """
            import threading

            class Switch:
                def __init__(self):
                    # lockfree:
                    self.peers = {}
                    self._t1 = threading.Thread(target=self._run_a, daemon=True)
                    self._t2 = threading.Thread(target=self._run_b, daemon=True)

                def _run_a(self):
                    self.peers["a"] = 1

                def _run_b(self):
                    self.peers["b"] = 2
            """
        }
        assert codes(run_fields(tmp_path, files).findings()) == ["CLNT012"]


class TestFieldSuppressions:
    BASE = """
    import threading
    from .libs import sync as libsync

    class Switch:
        def __init__(self):
            self._mtx = libsync.Mutex("fix.peers")
            self.peers = {}
            self._thr = threading.Thread(target=self._run, daemon=True)

        def _run(self):
            with self._mtx:
                self.peers["a"] = 1

        def snapshot(self):
            return dict(self.peers)TRAILER
    """

    def test_site_suppression_with_reason(self, tmp_path):
        files = {
            "switch.py": self.BASE.replace(
                "TRAILER",
                "  # cometlint: disable=CLNT011 -- "
                "snapshot copy, staleness is acceptable",
            )
        }
        assert run_fields(tmp_path, files).findings() == []

    def test_bare_suppression_is_ignored(self, tmp_path):
        files = {
            "switch.py": self.BASE.replace(
                "TRAILER", "  # cometlint: disable=CLNT011"
            )
        }
        assert codes(run_fields(tmp_path, files).findings()) == ["CLNT011"]


# ------------------------------------------------------------ the artifact


class TestFieldArtifact:
    def test_artifact_shape_and_witness(self, tmp_path):
        fields = run_fields(tmp_path, TestGuardInference.GUARDED)
        d = fields.fieldguards_dict()
        assert d["version"] == 1
        by_key = {(f["class"], f["field"]): f for f in d["fields"]}
        entry = by_key[("Switch", "peers")]
        assert entry["guard"] == ["fix.peers"]
        assert entry["lockfree"] == ""
        assert re.fullmatch(r"switch\.py:\d+", entry["witness"])
        assert entry["writes"] == 1 and entry["reads"] == 1
        # the locks registry is shared verbatim with the lock-order
        # artifact's vocabulary
        assert "fix.peers" in {lk["name"] for lk in d["locks"]}

    def test_artifact_is_deterministic(self, tmp_path):
        fields = run_fields(tmp_path, TestGuardInference.GUARDED)
        contexts, _ = parse_root(str(tmp_path))
        again = analyze_fields(analyze_contexts(contexts))
        assert again.fieldguards_dict() == fields.fieldguards_dict()

    def test_dot_marks_lockfree_dashed_and_guardless_red(self, tmp_path):
        files = {
            "switch.py": TestGuardInference.CLNT012["switch.py"],
            "store.py": """
            import threading

            class BlockStore:
                def __init__(self):
                    # lockfree: single writer, monotonic publish
                    self.base = 0
                    self._t1 = threading.Thread(target=self._a, daemon=True)
                    self._t2 = threading.Thread(target=self._b, daemon=True)

                def _a(self):
                    self.base = 1

                def _b(self):
                    self.base = 2
            """,
        }
        dot = run_fields(tmp_path, files).to_dot()
        assert '"BlockStore.base" [style=dashed];' in dot
        assert '"Switch.peers" [color=red];' in dot


# ------------------------------------------------ libs/sync record/enforce


class TestLocksetRuntime:
    def _reset(self):
        libsync.set_lockset_mode("off")
        libsync.reset_locksets()
        libsync._lockset_fields_path = None
        libsync._field_guards = None
        libsync.set_lock_order_mode("off")
        libsync.reset_lock_order()

    def _artifact(self, tmp_path) -> str:
        p = tmp_path / "fieldguards.json"
        p.write_text(
            json.dumps(
                {
                    "version": 1,
                    "generator": "test",
                    "locks": [],
                    "fields": [
                        {
                            "class": "Fix",
                            "field": "guarded",
                            "guard": ["fx.g"],
                            "lockfree": "",
                        },
                        {
                            "class": "Fix",
                            "field": "free",
                            "guard": [],
                            "lockfree": "single writer by design",
                        },
                    ],
                }
            )
        )
        return str(p)

    def test_record_mode_samples_field_and_held_locks(self):
        try:
            libsync.set_lockset_mode("record")
            libsync.reset_locksets()
            a = libsync.Mutex("ls.a")
            b = libsync.Mutex("ls.b")
            with a:
                with b:
                    libsync.lockset_note("Fix.guarded")
            libsync.lockset_note("Fix.free")
            obs = libsync.observed_locksets()
            assert ("Fix.guarded", frozenset({"ls.a", "ls.b"})) in obs
            assert ("Fix.free", frozenset()) in obs
            # witness points at this test file
            assert "test_lint_fields" in obs[
                ("Fix.guarded", frozenset({"ls.a", "ls.b"}))
            ]
        finally:
            self._reset()

    def test_enforce_passes_when_guard_held(self, tmp_path):
        try:
            libsync.set_lockset_mode(
                "enforce", fields_path=self._artifact(tmp_path)
            )
            libsync.reset_locksets()
            g = libsync.Mutex("fx.g")
            extra = libsync.Mutex("fx.extra")
            with g:
                with extra:  # superset of the guard is fine
                    libsync.lockset_note("Fix.guarded")
            assert (
                "Fix.guarded",
                frozenset({"fx.g", "fx.extra"}),
            ) in libsync.observed_locksets()
        finally:
            self._reset()

    def test_enforce_raises_when_guard_missing(self, tmp_path):
        try:
            libsync.set_lockset_mode(
                "enforce", fields_path=self._artifact(tmp_path)
            )
            other = libsync.Mutex("fx.other")
            with other:
                with pytest.raises(libsync.LocksetError) as ei:
                    libsync.lockset_note("Fix.guarded")
            assert "fx.g" in str(ei.value)
        finally:
            self._reset()

    def test_enforce_lets_lockfree_fields_through(self, tmp_path):
        try:
            libsync.set_lockset_mode(
                "enforce", fields_path=self._artifact(tmp_path)
            )
            libsync.lockset_note("Fix.free")  # nothing held: fine
        finally:
            self._reset()

    def test_enforce_rejects_unknown_field(self, tmp_path):
        # a seam the artifact has never seen means the artifact is
        # stale — fail loudly instead of silently under-checking
        try:
            libsync.set_lockset_mode(
                "enforce", fields_path=self._artifact(tmp_path)
            )
            with pytest.raises(libsync.LocksetError, match="regenerate"):
                libsync.lockset_note("Fix.unknown")
        finally:
            self._reset()

    def test_mode_validation(self):
        with pytest.raises(ValueError):
            libsync.set_lockset_mode("bogus")

    def test_lockset_mode_alone_instruments_locks(self):
        # the held-stack sampling needs name-tracking wrappers even
        # when deadlock detection and lock-order are both off
        try:
            libsync.set_lockset_mode("record")
            m = libsync.Mutex("ls.inst")
            assert hasattr(m, "_name")
        finally:
            self._reset()

    def test_off_mode_is_free(self):
        libsync.reset_locksets()
        libsync.lockset_note("Fix.guarded")
        assert libsync.observed_locksets() == {}


# ------------------------------------------------------ --changed CLI mode


class TestChangedMode:
    def _git(self, cwd, *argv):
        subprocess.run(
            ["git", "-c", "user.name=t", "-c", "user.email=t@t", *argv],
            cwd=cwd,
            check=True,
            capture_output=True,
        )

    def _findings(self, capsys) -> set[str]:
        out = capsys.readouterr().out
        return {
            line for line in out.splitlines() if ": CLNT" in line
        }

    def test_changed_matches_full_run_on_touched_files(
        self, tmp_path, monkeypatch, capsys
    ):
        proj = tmp_path / "proj"
        pkg = proj / "pkg"
        pkg.mkdir(parents=True)
        src = "import threading\nL = threading.Lock()\n"
        (pkg / "alpha.py").write_text(src)
        (pkg / "beta.py").write_text(src)
        self._git(proj, "init", "-q")
        self._git(proj, "add", "-A")
        self._git(proj, "commit", "-q", "-m", "seed")
        monkeypatch.chdir(proj)

        # pristine tree: nothing differs from HEAD, nothing is linted
        assert lint_main([str(pkg), "--no-baseline", "--changed"]) == 0
        assert self._findings(capsys) == set()

        # touch one file, add one untracked file
        (pkg / "alpha.py").write_text(src + "M = threading.RLock()\n")
        (pkg / "gamma.py").write_text(src)

        rc_full = lint_main([str(pkg), "--no-baseline", "--no-graph"])
        full = self._findings(capsys)
        rc_ch = lint_main([str(pkg), "--no-baseline", "--changed", "HEAD"])
        changed = self._findings(capsys)

        assert rc_full == 1 and rc_ch == 1
        # parity: the incremental run reports EXACTLY the full run's
        # findings restricted to files that differ from the ref
        # (modified + untracked), and none from the untouched file
        assert changed == {
            f
            for f in full
            if f.startswith(("alpha.py:", "gamma.py:"))
        }
        assert changed, "expected CLNT001 findings in touched files"
        assert not any(f.startswith("beta.py:") for f in changed)
        assert any(f.startswith("beta.py:") for f in full)

    def test_changed_with_bad_ref_is_a_usage_error(
        self, tmp_path, monkeypatch, capsys
    ):
        proj = tmp_path / "proj"
        pkg = proj / "pkg"
        pkg.mkdir(parents=True)
        (pkg / "a.py").write_text("x = 1\n")
        self._git(proj, "init", "-q")
        monkeypatch.chdir(proj)
        rc = lint_main(
            [str(pkg), "--no-baseline", "--changed", "no-such-ref"]
        )
        capsys.readouterr()
        assert rc == 2


# ------------------------------------------------------ engine-wide gates


class TestEngineWideFieldGate:
    @pytest.fixture(scope="class")
    def fields(self):
        contexts, errors = parse_root(PKG)
        assert not errors, errors
        return analyze_fields(analyze_contexts(contexts))

    def test_zero_unbaselined_field_findings(self):
        """The tentpole acceptance gate: every CLNT011/012 finding over
        the real engine is fixed, reason-suppressed inline, or
        justified in the baseline."""
        findings, errors = lint_root(PKG, ALL_CHECKERS)
        assert not errors, errors
        field_findings = [f for f in findings if f.code in FIELD_RULES]
        baseline = load_baseline(
            os.path.join(REPO, ".cometlint-baseline.json")
        )
        new, _matched, _stale = apply_baseline(field_findings, baseline)
        assert new == [], "unbaselined CLNT011/012:\n" + "\n".join(
            f.render() for f in new
        )

    def test_shipped_artifact_is_fresh(self, fields):
        """fieldguards.json (the artifact COMETBFT_TPU_LOCKSET=enforce
        validates against) must match the tree — regenerate with
        `python -m cometbft_tpu.devtools.lint --fields <path>`."""
        with open(SHIPPED_FIELDS, encoding="utf-8") as f:
            shipped = json.load(f)
        assert shipped == fields.fieldguards_dict(), (
            "stale fieldguards.json — regenerate via "
            "python -m cometbft_tpu.devtools.lint --fields "
            "cometbft_tpu/devtools/lint/graph/fieldguards.json"
        )

    def test_lock_registry_agrees_with_lockorder(self):
        """The two shipped artifacts must agree on the lock-name
        vocabulary, or the runtime sanitizers would validate the same
        run against two different worlds."""
        with open(SHIPPED_FIELDS, encoding="utf-8") as f:
            fg = json.load(f)
        with open(SHIPPED_GRAPH, encoding="utf-8") as f:
            lo = json.load(f)
        assert fg["locks"] == lo["locks"]

    def test_every_runtime_seam_is_in_the_artifact(self):
        """Every ``lockset_note("Class.field")`` seam in the engine
        names a field the shipped artifact knows, so enforce mode can
        never trip its unknown-field error on engine code."""
        with open(SHIPPED_FIELDS, encoding="utf-8") as f:
            known = {
                f"{e['class']}.{e['field']}"
                for e in json.load(f)["fields"]
            }
        seams: dict[str, str] = {}
        for dirpath, _dirs, names in os.walk(PKG):
            for name in names:
                if not name.endswith(".py"):
                    continue
                p = os.path.join(dirpath, name)
                if p.endswith(os.path.join("libs", "sync.py")):
                    continue  # the seam's own definition
                with open(p, encoding="utf-8") as fh:
                    for m in re.finditer(
                        r"lockset_note\(\s*\"([^\"]+)\"", fh.read()
                    ):
                        seams[m.group(1)] = p
        assert seams, "expected lockset_note seams in the engine"
        missing = {f: p for f, p in seams.items() if f not in known}
        assert not missing, missing

    def test_core_fsm_fields_guarded_as_documented(self, fields):
        """Spot-check the load-bearing guards the pipelined-heights
        refactor will lean on (docs/static-analysis.md 'Guarded
        fields')."""
        by_key = {
            (f["class"], f["field"]): f
            for f in fields.fieldguards_dict()["fields"]
        }
        assert "consensus.state" in by_key[
            ("ConsensusState", "state")
        ]["guard"]
        assert by_key[("CListMempool", "tx_map")]["guard"] == [
            "mempool.update"
        ]
        assert by_key[("CListMempool", "_pending_tx_keys")]["guard"] == [
            "mempool.update"
        ]
        assert "store.block_store._mtx" in by_key[
            ("BlockStore", "_height")
        ]["guard"]
        assert "p2p.switch.peers" in by_key[("Switch", "_peers")]["guard"]
        assert "vote_set" in by_key[("VoteSet", "votes")]["guard"]
        assert by_key[("PartSet", "count")]["lockfree"]

    def test_fieldguards_deterministic(self, fields):
        contexts, _ = parse_root(PKG)
        again = analyze_fields(analyze_contexts(contexts))
        assert again.fieldguards_dict() == fields.fieldguards_dict()
