"""`ddlt lint` — the static-analysis subsystem's own test coverage.

Two halves:

- **detection pins** over the seeded-violation corpus
  (``tests/fixtures/lint_violations/``): every checker — host-sync,
  stale-marker, donation, collective-signature, callback-in-jit,
  dtype-audit, sharding-coverage, fault-coverage — must catch exactly its
  planted bug with a file:line finding, and must NOT reproduce the regex
  era's false-positive classes (``float(`` in strings/comments, alias
  renames, ``jnp.asarray`` uploads);
- **clean-tree pins**: both analyzer layers report zero findings over the
  live tree (THE tier-1 gate — ``bench.py --lint`` and ``make lint``
  enforce the same invariant), and the program registry actually covers
  the contracted programs (train step both comm paths, prefill/decode/
  verify on both KV layouts, quantized variants) with non-vacuous
  donation counts.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from distributeddeeplearning_tpu.analysis import format_findings, run_lint
from distributeddeeplearning_tpu.analysis import host_sync
from distributeddeeplearning_tpu.analysis.fault_coverage import (
    check_fault_coverage,
)
from distributeddeeplearning_tpu.analysis.regions import (
    ALL_REGIONS,
    HotRegion,
)
from distributeddeeplearning_tpu.cli.main import main as cli_main

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "lint_violations"


def _line_of(path: Path, needle: str) -> int:
    for i, line in enumerate(path.read_text().splitlines(), 1):
        if needle in line:
            return i
    raise AssertionError(f"{needle!r} not found in {path}")


def _fixture_region(**overrides) -> HotRegion:
    kw = dict(
        name="fixture-loop",
        module="<fixture>",
        qualname="hot_loop",
        locator="for x in xs",
        landmarks=(),
        sync_budget=0,
    )
    kw.update(overrides)
    return HotRegion(**kw)


# --------------------------------------------------------------------------
# layer 1: host-sync checker detection pins
# --------------------------------------------------------------------------


class TestHostSyncChecker:
    def test_catches_every_planted_sync_with_file_line(self):
        path = FIXTURES / "host_sync_violation.py"
        region = _fixture_region(landmarks=("engine.decode",))
        findings = host_sync.check_region(region, path=str(path))
        syncs = [f for f in findings if f.checker == "host-sync"]
        got = {f.line for f in syncs}
        want = {
            _line_of(path, "float(out)"),
            _line_of(path, "renamed_np.asarray(out)"),
            _line_of(path, "local_asarray(out)"),
            _line_of(path, "renamed_get(out)"),
            _line_of(path, "out.item()"),
            # banned targets passed as bare references (map/key=) sync
            # per element just as hard — the regex caught these as
            # substrings, so the AST checker must too
            _line_of(path, "map(renamed_np.asarray"),
            _line_of(path, "key=renamed_get"),
        }
        assert got == want, format_findings(findings)
        assert all(f.path.endswith("host_sync_violation.py") for f in syncs)
        # alias resolution names the canonical target in the message
        assert any("numpy.asarray" in f.message for f in syncs)
        assert any("jax.device_get" in f.message for f in syncs)
        assert any("reference" in f.message for f in syncs)

    def test_regex_false_positive_classes_stay_clean(self):
        """The known false positives of the old indentation+regex lint:
        banned tokens inside strings and comments, and the jnp.asarray
        device upload — none may produce a finding."""
        path = FIXTURES / "host_sync_violation.py"
        region = _fixture_region(landmarks=("engine.decode",))
        findings = host_sync.check_region(region, path=str(path))
        clean_lines = {
            _line_of(path, "inside a string"),
            _line_of(path, "commented float("),
            _line_of(path, "jnp.asarray(x)"),
        }
        assert not clean_lines & {f.line for f in findings}, (
            format_findings(findings)
        )

    def test_stale_marker_is_a_finding(self):
        """Exactly ONE stale finding: the planted dead waiver — the
        colon-less prose comment mentioning 'sync-ok markers' must not
        register as a (phantom) waiver at all."""
        path = FIXTURES / "stale_marker.py"
        region = _fixture_region(landmarks=("step(x)",), sync_budget=1)
        findings = host_sync.check_region(region, path=str(path))
        assert [f.checker for f in findings] == ["stale-marker"], (
            format_findings(findings)
        )
        assert findings[0].line == _line_of(path, "PLANTED dead waiver")

    def test_live_marker_waives_and_counts_against_budget(self):
        path = FIXTURES / "stale_marker.py"
        # budget 1 satisfied by the live marked float() — no budget
        # finding, no host-sync finding for the marked line
        region = _fixture_region(landmarks=(), sync_budget=1)
        findings = host_sync.check_region(region, path=str(path))
        assert not [f for f in findings if f.checker == "host-sync"]
        assert not [f for f in findings if f.checker == "allowlist-budget"]

    def test_budget_mismatch_is_a_finding(self):
        path = FIXTURES / "stale_marker.py"
        region = _fixture_region(sync_budget=2)  # only 1 live marker
        findings = host_sync.check_region(region, path=str(path))
        budget = [f for f in findings if f.checker == "allowlist-budget"]
        assert len(budget) == 1 and "expects exactly 2" in budget[0].message

    def test_missing_landmark_is_a_finding(self):
        path = FIXTURES / "stale_marker.py"
        region = _fixture_region(
            landmarks=("engine.decode(",), sync_budget=1
        )
        findings = host_sync.check_region(region, path=str(path))
        assert any(f.checker == "landmark" for f in findings)

    def test_moved_region_surfaces_as_finding_not_crash(self):
        path = FIXTURES / "stale_marker.py"
        region = _fixture_region(locator="while nothing matches this")
        findings = host_sync.check_region(region, path=str(path))
        assert [f.checker for f in findings] == ["region"]
        assert "no longer matches" in findings[0].message

    def test_strict_region_ignores_markers(self):
        """Jitted-builder regions: a marked sync is still a finding."""
        path = FIXTURES / "stale_marker.py"
        region = _fixture_region(honor_markers=False)
        findings = host_sync.check_region(region, path=str(path))
        syncs = [f for f in findings if f.checker == "host-sync"]
        assert len(syncs) == 1
        assert "markers are not honored" in syncs[0].message

    def test_goodput_record_float_coercion_is_caught(self):
        """The goodput-ledger seeded fixture: a ledger category recorded
        via a host-syncing ``float(...)`` on the mark()-shaped record
        path — the exact class the real ``obs-goodput-mark`` region bans
        with its zero budget — is caught at file:line (and the decoy
        ``float(`` inside the string is not)."""
        path = FIXTURES / "goodput_violation.py"
        region = _fixture_region(
            qualname="record_goodput",
            locator=None,  # the whole record function is the region
            landmarks=("time.perf_counter()",),
            sync_budget=0,
        )
        findings = host_sync.check_region(region, path=str(path))
        syncs = [f for f in findings if f.checker == "host-sync"]
        assert [f.line for f in syncs] == [
            _line_of(path, "float(seconds)")
        ], format_findings(findings)
        assert _line_of(path, "in this string") not in {
            f.line for f in findings
        }
        # the live-tree region this fixture mirrors is registered with a
        # zero budget — and the real record path stays clean under it
        from distributeddeeplearning_tpu.analysis.regions import get_region

        real = get_region("obs-goodput-mark")
        assert real.sync_budget == 0
        assert not host_sync.check_region(real), format_findings(
            host_sync.check_region(real)
        )


# --------------------------------------------------------------------------
# fault-coverage cross-check
# --------------------------------------------------------------------------


class TestFaultCoverage:
    HOOKS = {
        "covered_kind": ("fire_covered",),
        "orphan_kind": ("fire_orphan",),
        "ckpt_corrupt": ("take_ckpt_corrupt",),
    }

    def test_orphan_kind_is_caught_with_file_line(self):
        faults = FIXTURES / "faultpkg" / "faults.py"
        findings = check_fault_coverage(
            faults_path=str(faults),
            package_root=str(FIXTURES / "faultpkg"),
            kind_hooks=self.HOOKS,
        )
        # orphan_kind and the checkpoint kind below are both uncovered
        orphans = [f for f in findings if "orphan_kind" in f.message]
        assert len(orphans) == 1, format_findings(findings)
        f = orphans[0]
        assert f.checker == "fault-coverage"
        assert f.path.endswith("faults.py")
        assert f.line == _line_of(faults, "KINDS = ")

    def test_orphan_checkpoint_fault_kind_is_caught(self):
        """A checkpoint-durability kind whose injection hook exists but is
        never CALLED (comment/string decoys planted in the fixture) must
        be reported — a renamed ``take_ckpt_corrupt`` call-site would
        silently drop corruption chaos from every bench."""
        findings = check_fault_coverage(
            faults_path=str(FIXTURES / "faultpkg" / "faults.py"),
            package_root=str(FIXTURES / "faultpkg"),
            kind_hooks=self.HOOKS,
        )
        ckpt = [f for f in findings if "ckpt_corrupt" in f.message]
        assert len(ckpt) == 1, format_findings(findings)
        assert "no injection call-site" in ckpt[0].message
        assert "take_ckpt_corrupt" in ckpt[0].message

    def test_renamed_hook_is_caught(self):
        findings = check_fault_coverage(
            faults_path=str(FIXTURES / "faultpkg" / "faults.py"),
            package_root=str(FIXTURES / "faultpkg"),
            kind_hooks={"covered_kind": ("fire_covered_RENAMED",),
                        "orphan_kind": ("fire_orphan",),
                        "ckpt_corrupt": ("take_ckpt_corrupt",)},
        )
        assert any(
            "not a FaultPlan method" in f.message for f in findings
        ), format_findings(findings)

    def test_clean_tree_fault_coverage(self):
        assert check_fault_coverage() == []


# --------------------------------------------------------------------------
# layer 2: program-audit detection pins (seeded bad programs)
# --------------------------------------------------------------------------


class TestProgramAuditDetections:
    @pytest.fixture(scope="class")
    def fixtures(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "lint_violation_programs", FIXTURES / "programs.py"
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_lost_donation_caught(self, fixtures):
        from distributeddeeplearning_tpu.analysis.program_audit import (
            check_program,
        )

        findings = check_program(fixtures.lost_donation())
        assert [f.checker for f in findings] == ["donation"], (
            format_findings(findings)
        )
        assert findings[0].path.endswith("programs.py")
        assert findings[0].line > 0

    def test_callback_in_jit_caught(self, fixtures):
        from distributeddeeplearning_tpu.analysis.program_audit import (
            check_program,
        )

        findings = check_program(fixtures.callback_in_jit())
        assert [f.checker for f in findings] == ["callback-in-jit"], (
            format_findings(findings)
        )
        # jax 0.9.0 binds `jax.debug.print` as the `debug_print` primitive
        assert "debug_print" in findings[0].message

    def test_hoisted_collective_caught(self, fixtures):
        from distributeddeeplearning_tpu.analysis.program_audit import (
            CollectiveContract,
            check_collective_contract,
        )

        jaxpr = fixtures.hoisted_collective()
        findings = check_collective_contract(
            jaxpr, CollectiveContract(in_scan_reduce_scatter_min=1),
            name="fixture.hoisted", path="fixture", line=1,
        )
        msgs = " | ".join(f.message for f in findings)
        assert any(f.checker == "collective-signature" for f in findings)
        assert "INSIDE the accumulation scan" in msgs  # no in-scan RS
        assert "hoisted all-reduce" in msgs  # the post-scan psum

    def test_f32_history_returned_caught(self, fixtures):
        from distributeddeeplearning_tpu.analysis.program_audit import (
            check_program,
        )

        findings = check_program(fixtures.f32_history_returned())
        dtype = [f for f in findings if f.checker == "dtype-audit"]
        assert len(dtype) == 1, format_findings(findings)
        assert "RETURNS" in dtype[0].message

    def test_bf16_history_returned_caught(self, fixtures):
        """Half-width evasion: dequantizing to bf16 instead of f32 is
        the same materialization regression and must still be caught."""
        from distributeddeeplearning_tpu.analysis.program_audit import (
            check_program,
        )

        findings = check_program(fixtures.bf16_history_returned())
        dtype = [f for f in findings if f.checker == "dtype-audit"]
        assert len(dtype) == 1, format_findings(findings)
        assert "RETURNS" in dtype[0].message

    def test_f32_history_intermediate_caught(self, fixtures):
        """The PR-12 extension: a history-granular dequant that never
        reaches an output or a write (reduced away in-program) passes
        the old checks but must fail the strict intermediate audit the
        flash-decode records arm via ``int8_head_dim``."""
        import dataclasses

        from distributeddeeplearning_tpu.analysis.program_audit import (
            check_program,
        )

        rec = fixtures.f32_history_intermediate()
        findings = check_program(rec)
        inter = [f for f in findings if "intermediate" in f.message]
        assert inter, format_findings(findings)
        assert "`mul`" in inter[0].message
        # the SAME program with the strict audit unarmed passes clean —
        # pins that the catch above is the new checker, nothing else
        relaxed = dataclasses.replace(rec, int8_head_dim=None)
        assert not check_program(relaxed), format_findings(
            check_program(relaxed)
        )

    def test_gather_path_fails_strict_intermediate_audit(self):
        """Non-vacuity for the clean-tree gate: arming the strict audit
        on the LEGACY gather int8 decode programs (which the registry
        deliberately registers relaxed) produces findings — so the flash
        programs passing it means the fused read actually differs."""
        import dataclasses

        from distributeddeeplearning_tpu.analysis.program_audit import (
            build_program_records,
            check_program,
        )

        records = {r.name: r for r in build_program_records()}
        for name in (
            "serve.paged.int8_gather.decode",
            "serve.dense.int8_gather.decode",
        ):
            rec = records[name]
            assert rec.int8_head_dim is None, name  # registered relaxed
            armed = dataclasses.replace(rec, int8_head_dim=8)
            inter = [
                f for f in check_program(armed)
                if "intermediate" in f.message
            ]
            assert inter, f"{name}: gather path passed the strict audit"

    def test_f32_history_written_caught(self, fixtures):
        from distributeddeeplearning_tpu.analysis.program_audit import (
            check_program,
        )

        findings = check_program(fixtures.f32_history_written())
        dtype = [f for f in findings if f.checker == "dtype-audit"]
        assert len(dtype) == 1, format_findings(findings)
        assert "WRITES" in dtype[0].message
        assert "dynamic_update_slice" in dtype[0].message

    def test_unsharded_leaf_caught(self, fixtures):
        from distributeddeeplearning_tpu.analysis.program_audit import (
            check_tree_coverage,
        )

        tree_abs, shardings = fixtures.unsharded_leaf()
        findings = check_tree_coverage(
            tree_abs, shardings, name="fixture.cache", path="fixture",
            line=1,
        )
        assert len(findings) == 1, format_findings(findings)
        assert findings[0].checker == "sharding-coverage"
        assert "k_zero_point" in findings[0].message

    def test_rule_table_fallthrough_caught(self, fixtures):
        """The layout-engine sibling of the unsharded-leaf class: a leaf
        name no LAYOUT_RULES pattern matches must surface as a
        sharding-coverage finding at the planted file:line — and the
        matched sibling leaf (qkv) must NOT fire."""
        from distributeddeeplearning_tpu.analysis.program_audit import (
            check_rule_fallthrough,
        )

        path = FIXTURES / "programs.py"
        line = _line_of(path, "wq_lora_adapter")
        findings = check_rule_fallthrough(
            fixtures.rule_fallthrough_tree(), prefix="params",
            name="fixture.params", path=str(path), line=line,
        )
        assert len(findings) == 1, format_findings(findings)
        f = findings[0]
        assert f.checker == "sharding-coverage"
        assert "params/blocks/0/wq_lora_adapter" in f.message
        assert f.path.endswith("programs.py") and f.line == line
        assert "LAYOUT_RULES" in (f.hint or "")

    def test_rule_table_audit_armed_on_live_tree(self):
        """Non-vacuity: the hot-program rule-table sweep inside
        check_sharding_coverage actually consults the layout table — an
        empty rule table must produce fallthrough findings pointing at
        parallel/sharding.py, while the real table stays clean."""
        from unittest import mock

        from distributeddeeplearning_tpu.analysis import program_audit
        from distributeddeeplearning_tpu.parallel import sharding

        assert program_audit.check_sharding_coverage() == []
        with mock.patch.object(sharding, "LAYOUT_RULES", ()):
            findings = program_audit.check_sharding_coverage()
        fallthrough = [
            f for f in findings if "matches NO rule" in f.message
        ]
        assert fallthrough, format_findings(findings)
        assert all(
            f.path.endswith("parallel/sharding.py") and f.line > 0
            for f in fallthrough
        )


# --------------------------------------------------------------------------
# clean-tree gates + registry coverage pins
# --------------------------------------------------------------------------


class TestCleanTree:
    def test_ast_layer_zero_findings(self):
        findings = run_lint(programs=False)
        assert not findings, format_findings(findings, str(REPO))

    def test_program_audits_zero_findings(self):
        """THE acceptance gate: donation + collective signature pinned
        for the train step (both comm paths) and prefill/decode/verify
        on both KV layouts (+ quantized variants), via abstract tracing
        on the CPU platform — zero findings on the clean tree."""
        from distributeddeeplearning_tpu.analysis.program_audit import (
            run_program_audits,
            skipped_audits,
        )

        findings = run_program_audits()
        assert not findings, format_findings(findings, str(REPO))
        # under the test env's 8-device virtual pod NOTHING may skip —
        # a silent skip would make this gate weaker than it reads
        assert skipped_audits() == []

    def test_single_shard_skip_is_reported_not_silent(self):
        """On a REAL 1-device backend (no virtual pod) the implicit-path
        collective audit cannot run — the sweep must still pass clean
        AND report the skip through skipped_audits(), never swallow it
        (a silent skip would make `bench.py --lint` on a 1-device box a
        weaker gate than `make lint` with no indication)."""
        code = (
            "import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "os.environ.pop('XLA_FLAGS', None)\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "assert len(jax.devices()) == 1, jax.devices()\n"
            "from distributeddeeplearning_tpu.analysis import "
            "program_audit\n"
            "f = program_audit.run_program_audits()\n"
            "assert not f, [x.message for x in f]\n"
            "skips = program_audit.skipped_audits()\n"
            "assert len(skips) == 1 and 'collective-signature' in "
            "skips[0], skips\n"
            "print('SKIP_REPORTED_OK')\n"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, timeout=280, cwd=str(REPO),
        )
        assert "SKIP_REPORTED_OK" in out.stdout, out.stdout + out.stderr

    def test_program_registry_covers_the_contract(self):
        """The zero-findings gate above is only as strong as the
        registry — pin that the contracted programs are actually in it,
        with donation expectations armed."""
        from distributeddeeplearning_tpu.analysis.program_audit import (
            build_program_records,
        )

        records = {r.name: r for r in build_program_records()}
        required = [
            "serve.dense.f32.prefill", "serve.dense.f32.decode",
            "serve.dense.int8.decode", "serve.dense.w_int8.decode",
            "serve.paged.f32.prefill_chunk", "serve.paged.f32.decode",
            "serve.paged.int8.decode", "spec.dense.verify",
            "spec.paged.verify", "spec.dense.rollback",
            "spec.dense.draft",
            # PR 12: flash is the default kernel, and the legacy gather
            # engines stay registered (still selectable end-to-end)
            "serve.dense.int8_gather.decode",
            "serve.paged.int8_gather.decode",
            "serve.paged.int8_gather.prefill_chunk",
        ]
        for name in required:
            assert name in records, sorted(records)
        for name in required:
            if name.endswith((".decode", ".verify", ".rollback")):
                assert records[name].donate_min >= 2, name
        # the quantized variants run the dtype audit
        assert records["serve.dense.int8.decode"].int8_history_len
        assert records["serve.paged.int8.decode"].int8_history_len
        # the default (flash) int8 programs arm the STRICT intermediate
        # audit; the gather variants are relaxed by design
        for name in (
            "serve.dense.int8.decode", "serve.paged.int8.decode",
            "serve.paged.int8.prefill_chunk",
        ):
            assert records[name].int8_head_dim, name
        for name in (
            "serve.dense.int8_gather.decode",
            "serve.paged.int8_gather.decode",
        ):
            assert records[name].int8_head_dim is None, name

    def test_donation_counts_are_exact_not_vacuous(self):
        """The lowered dense decode aliases exactly its cache leaves:
        2 (k, v) for f32, 4 (+scales) for int8 — pins that the alias
        annotation counting measures what it claims."""
        from distributeddeeplearning_tpu.analysis.program_audit import (
            ALIAS_ANNOTATION,
            build_program_records,
        )

        records = {r.name: r for r in build_program_records()}
        for name, expect in (
            ("serve.dense.f32.decode", 2),
            ("serve.dense.int8.decode", 4),
        ):
            rec = records[name]
            text = rec.jitted.trace(*rec.args).lower().as_text()
            assert text.count(ALIAS_ANNOTATION) == expect, name


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------


class TestEntryPoints:
    def test_cli_lint_json_clean(self, capsys):
        rc = cli_main(["lint", "--no-programs", "--json"])
        out = capsys.readouterr().out
        assert rc == 0
        assert json.loads(out) == []

    def test_cli_lint_nonzero_on_findings(self, capsys, monkeypatch):
        """Exit-code contract: any finding -> rc 1, file:line printed."""
        import distributeddeeplearning_tpu.analysis as analysis_pkg
        from distributeddeeplearning_tpu.analysis.core import Finding

        monkeypatch.setattr(
            analysis_pkg, "run_lint",
            lambda programs=True: [
                Finding("host-sync", "x.py", 3, "planted", hint="fix it")
            ],
        )
        rc = cli_main(["lint", "--no-programs"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "x.py:3" in out and "planted" in out and "fix it" in out

    def test_bench_lint_preflight_wired(self):
        """`bench.py --lint` exists and gates artifact production (the
        flag parses; the preflight body runs ``ddlt lint`` before any
        benchmark dispatch)."""
        src = (REPO / "bench.py").read_text()
        assert "--lint" in src
        idx_lint = src.index('"lint"],')
        idx_dispatch = src.index("return _dispatch(args)")
        assert idx_lint < idx_dispatch
        help_text = subprocess.run(
            [sys.executable, str(REPO / "bench.py"), "--help"],
            capture_output=True, text=True, timeout=120,
        ).stdout
        assert "--lint" in help_text

    def test_make_lint_target_exists(self):
        mk = (REPO / "Makefile").read_text()
        assert "lint:" in mk and "cli.main lint" in mk


def test_registry_regions_all_resolve():
    """Every registry entry must locate its function+loop in the live
    source (a 'region' finding anywhere means the registry rotted)."""
    for region in ALL_REGIONS:
        findings = host_sync.check_region(region)
        assert not [f for f in findings if f.checker == "region"], (
            region.name
        )
