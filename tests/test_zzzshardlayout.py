"""Sharding-contract layer tests: SpecLayout, shardlint JL010+, shard
audit golden machinery.

Named to sort LAST (tier-1 870 s budget convention): everything here is
cheap — AST fixtures, pure diff functions, and spec pins on the virtual
8-device CPU mesh. The expensive compile-based audit itself runs in the
tier-1 verify command (scripts/shard_audit.py, before pytest), so these
tests cover the logic around it, not the compile.
"""

from __future__ import annotations

import copy
import dataclasses
import importlib.util
import json
import os.path as osp
import subprocess
import sys
import textwrap

import pytest

from dexiraft_tpu.analysis import jaxlint, shardaudit, shardlint

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))
GATE = osp.join(REPO, "scripts", "lint_gate.py")


def _lint(src: str, path: str = "dexiraft_tpu/somefile.py"):
    return jaxlint.lint_source(textwrap.dedent(src), path)


def _rules(findings):
    return sorted({f.rule for f in findings})


# --------------------------------------------------------------------------
# shardlint rules: positive + negative fixtures per rule
# --------------------------------------------------------------------------


class TestJL010InlineSpec:
    def test_partition_spec_literal_flagged(self):
        fs = _lint("""
            from jax.sharding import PartitionSpec as P
            spec = P("x", None)
        """)
        assert "JL010" in _rules(fs)

    def test_named_sharding_literal_flagged(self):
        fs = _lint("""
            from jax.sharding import NamedSharding, PartitionSpec
            ns = NamedSharding(mesh, PartitionSpec())
        """)
        assert [f for f in fs if f.rule == "JL010"]

    def test_layout_module_exempt(self):
        fs = _lint("""
            from jax.sharding import PartitionSpec as P
            spec = P("data")
        """, path=shardlint.LAYOUT_PATH)
        assert "JL010" not in _rules(fs)

    def test_layout_drawn_spec_clean(self):
        fs = _lint("""
            from dexiraft_tpu.parallel.layout import LAYOUT, named
            s = named(mesh, LAYOUT.batch_spatial())
        """)
        assert "JL010" not in _rules(fs)

    def test_suppression_comment(self):
        fs = _lint("""
            from jax.sharding import PartitionSpec as P
            spec = P("x")  # jaxlint: disable=JL010
        """)
        assert "JL010" not in _rules(fs)


class TestJL011AdhocMeshAxis:
    def test_mesh_ctor_flagged(self):
        fs = _lint("""
            from jax.sharding import Mesh
            import numpy as np
            m = Mesh(np.asarray(devs), ("x",))
        """)
        assert "JL011" in _rules(fs)

    def test_axis_name_string_in_collective_flagged(self):
        fs = _lint("""
            import jax
            def f():
                return jax.lax.axis_index("seq")
        """)
        assert "JL011" in _rules(fs)

    def test_axis_keyword_string_flagged(self):
        fs = _lint("""
            import jax
            def f(x):
                return jax.lax.psum(x, axis_name="data")
        """)
        assert "JL011" in _rules(fs)

    def test_unrelated_data_string_clean(self):
        # 'data' as a filesystem path component is NOT an axis name
        fs = _lint("""
            import os
            root = os.path.join(base, "data")
            d = {"data": 1}
        """)
        assert "JL011" not in _rules(fs)

    def test_layout_constant_clean(self):
        fs = _lint("""
            import jax
            from dexiraft_tpu.parallel.layout import SEQ_AXIS
            def f():
                return jax.lax.axis_index(SEQ_AXIS)
        """)
        assert "JL011" not in _rules(fs)

    def test_layout_module_exempt(self):
        fs = _lint("""
            from jax.sharding import Mesh
            import numpy as np
            m = Mesh(np.asarray(devs), ("data",))
        """, path=shardlint.LAYOUT_PATH)
        assert "JL011" not in _rules(fs)


class TestJL012RawSpecConstraint:
    def test_inline_spec_flagged(self):
        fs = _lint("""
            import jax
            from jax.sharding import PartitionSpec as P
            def f(x):
                return jax.lax.with_sharding_constraint(x, P("x"))
        """)
        assert "JL012" in _rules(fs)

    def test_named_spec_clean(self):
        fs = _lint("""
            import jax
            def f(x, spec):
                return jax.lax.with_sharding_constraint(x, spec)
        """)
        assert "JL012" not in _rules(fs)


class TestJL013UnpinnedMeshJit:
    def test_unpinned_state_jit_on_mesh_path_flagged(self):
        fs = _lint("""
            import jax
            def make_step(cfg, mesh=None):
                def step(state, batch):
                    return state
                return jax.jit(step, donate_argnums=0)
        """)
        assert "JL013" in _rules(fs)

    def test_mesh_none_branch_exempt(self):
        fs = _lint("""
            import jax
            def make_step(cfg, mesh=None):
                def step(state, batch):
                    return state
                if mesh is None:
                    return jax.jit(step, donate_argnums=0)
                return jax.jit(step, in_shardings=(a, b),
                               out_shardings=(a, a), donate_argnums=0)
        """)
        assert "JL013" not in _rules(fs)

    def test_variables_threading_covered(self):
        fs = _lint("""
            import jax
            def make_eval(cfg, mesh=None):
                def step(variables, image1):
                    return image1
                return jax.jit(step)
        """)
        assert "JL013" in _rules(fs)

    def test_no_mesh_param_exempt(self):
        # single-chip builders (dexined_cli style) have no mesh concept
        fs = _lint("""
            import jax
            def make_step(cfg):
                def step(state, batch):
                    return state
                return jax.jit(step, donate_argnums=0)
        """)
        assert "JL013" not in _rules(fs)

    def test_partial_pin_flagged(self):
        fs = _lint("""
            import jax
            def make_step(cfg, mesh=None):
                def step(state, batch):
                    return state
                return jax.jit(step, in_shardings=(a, b), donate_argnums=0)
        """)
        assert "JL013" in _rules(fs)


class TestRuleRegistration:
    def test_rules_merged_into_jaxlint(self):
        for rule in shardlint.RULES:
            assert rule in jaxlint.RULES

    def test_axes_mirror_the_live_layout(self):
        """shardlint is jax-free so it pins the axis names; they must
        equal the real SpecLayout's axes."""
        from dexiraft_tpu.parallel.layout import LAYOUT

        live = {LAYOUT.data_axis, LAYOUT.fsdp_axis, LAYOUT.seq_axis}
        assert set(shardlint.LAYOUT_AXES) == live


# --------------------------------------------------------------------------
# SpecLayout pins
# --------------------------------------------------------------------------


class TestSpecLayout:
    def test_frozen(self):
        from dexiraft_tpu.parallel.layout import LAYOUT

        with pytest.raises(dataclasses.FrozenInstanceError):
            LAYOUT.data_axis = "other"

    def test_canonical_specs(self):
        from dexiraft_tpu.parallel.layout import LAYOUT, spec_str

        assert spec_str(LAYOUT.replicated()) == "P()"
        assert spec_str(LAYOUT.params()) == "P()"
        assert spec_str(LAYOUT.opt_state()) == "P()"
        assert spec_str(LAYOUT.batch()) == "P('data')"
        assert spec_str(LAYOUT.batch_spatial()) == "P('data', 'seq')"
        assert spec_str(LAYOUT.batch_spatial_compute()) == \
            "P('data', 'seq')"
        assert spec_str(LAYOUT.carry()) == "P('data')"
        assert spec_str(LAYOUT.corr_query_rows()) == \
            "P(None, 'seq', None, None)"
        assert spec_str(LAYOUT.fsdp_params()) == "P('fsdp')"

    def test_complete_coverage(self):
        """Every canonical spec surface the audit golden accounts for —
        adding one means extending the golden + docs too."""
        from dexiraft_tpu.parallel.layout import SpecLayout

        expected = {"replicated", "params", "opt_state", "fsdp_params",
                    "param_leaf_spec", "batch", "batch_spatial",
                    "batch_spatial_compute", "carry",
                    "corr_query_rows", "batch_for", "corr_volume",
                    "corr_fmaps", "corr_window", "data_size", "has_seq", "has_fsdp",
                    "fsdp_size", "seq_size"}
        public = {n for n in dir(SpecLayout) if not n.startswith("_")
                  and callable(getattr(SpecLayout, n))}
        assert public == expected

    def test_mesh_dependent_specs(self):
        from dexiraft_tpu.parallel.layout import (
            LAYOUT,
            make_mesh,
            make_mesh_2d,
            spec_str,
        )

        m1 = make_mesh()
        m2 = make_mesh_2d(4, 2)
        assert spec_str(LAYOUT.batch_for(m1)) == "P('data')"
        assert spec_str(LAYOUT.batch_for(m2)) == "P('data', 'seq')"
        assert spec_str(LAYOUT.corr_volume(m2)) == "P('data', 'seq')"
        assert spec_str(LAYOUT.corr_fmaps(m2)) == "P('data', 'seq')"
        # the lookup's kernels, (S1, S2, B, H*W): an axis only where it
        # divides; nothing to split on one device or with no mesh axis left
        whole, per_query = LAYOUT.corr_window(m2.abstract_mesh, (46, 62, 8, 64))
        assert spec_str(whole) == "P(None, None, 'data', 'seq')"
        assert spec_str(per_query) == "P('data', 'seq')"
        whole, per_query = LAYOUT.corr_window(m2.abstract_mesh, (46, 62, 6, 64))
        assert spec_str(whole) == "P(None, None, None, 'seq')"
        assert spec_str(per_query) == "P(None, 'seq')"
        assert LAYOUT.corr_window(m2.abstract_mesh, (46, 62, 6, 63)) is None
        import jax

        one = make_mesh(jax.devices()[:1]).abstract_mesh
        assert LAYOUT.corr_window(one, (46, 62, 8, 64)) is None
        assert LAYOUT.data_size(m2) == 4
        assert LAYOUT.has_seq(m2) and not LAYOUT.has_seq(m1)
        assert LAYOUT.seq_size(m2) == 2 and LAYOUT.seq_size(m1) == 1

    def test_make_train_mesh_policy(self):
        """The glue that used to live inline in train_cli: largest
        device count dividing the batch."""
        from dexiraft_tpu.parallel.layout import make_train_mesh

        assert make_train_mesh(8).size == 8
        assert make_train_mesh(6).size == 6
        assert make_train_mesh(3).size == 3
        assert make_train_mesh(7).size == 7

    def test_mesh_compat_surface(self):
        """parallel.mesh re-exports the layout's implementations."""
        from dexiraft_tpu.parallel import layout, mesh

        assert mesh.make_mesh is layout.make_mesh
        assert mesh.batch_putter is layout.batch_putter
        assert mesh.LAYOUT is layout.LAYOUT
        assert mesh.DATA_AXIS == layout.LAYOUT.data_axis

    def test_replicated_ok_covers_state_groups(self):
        """Since the fsdp axis went live, params/opt_state carry NO
        replicated-by-design exemption — the size canary is armed on
        them (tests/test_zzzfsdp.py exercises it); only the genuinely
        global groups stay pinned."""
        from dexiraft_tpu.parallel.layout import REPLICATED_OK

        assert "batch_stats" in REPLICATED_OK
        assert "params" not in REPLICATED_OK
        assert "opt_state" not in REPLICATED_OK


# --------------------------------------------------------------------------
# shard audit: golden machinery (pure — no compiles)
# --------------------------------------------------------------------------


def _golden() -> dict:
    return shardaudit.load_golden()


class TestGoldenFile:
    def test_shipped_golden_loads_and_covers_all_steps(self):
        g = _golden()
        # serve_encode / serve_refine: the split-model streaming
        # signatures (PR 14) audited beside the monolithic serve step
        assert set(g["steps"]) == {"train", "eval", "serve",
                                   "serve_encode", "serve_refine"}
        from dexiraft_tpu.parallel.layout import LAYOUT

        assert g["axes"] == {"data": LAYOUT.data_axis,
                             "fsdp": LAYOUT.fsdp_axis,
                             "seq": LAYOUT.seq_axis}
        assert g["steps"]["train"]["mesh"] == shardaudit.TRAIN_MESH
        assert g["steps"]["serve"]["mesh"] == shardaudit.SERVE_MESH
        assert g["steps"]["serve_encode"]["mesh"] == shardaudit.SERVE_MESH
        assert g["steps"]["serve_refine"]["mesh"] == shardaudit.SERVE_MESH

    def test_volume_free_golden_with_fmap_canary(self):
        """ISSUE 12 pin: the production eval/serve config is the flash-
        blocked kernel, so the audit passes WITHOUT the materialized
        all-pairs volume — the corr_volume declared group is gone, and
        the canary is armed on the streamed fmap set instead (still
        over the 64 MB tripwire if ever pinned replicated)."""
        declared = _golden()["declared"]
        assert "corr_volume" not in declared
        g = declared["corr_fmaps"]
        assert not g["replicated"] and not g["flagged"]
        assert g["total_mb"] > shardaudit.DEFAULT_THRESHOLD_MB
        # the remaining groups keep the tripwire armed
        assert {"batch", "carry", "params", "opt_state"} <= set(declared)

    def test_params_replicated_by_design(self):
        g = _golden()["declared"]["params"]
        assert g["replicated"] and not g["flagged"]

    def test_golden_hash_stable(self):
        h1 = shardaudit.golden_hash()
        h2 = shardaudit.golden_hash()
        assert h1 == h2 and len(h1) == 40


class TestGoldenDiff:
    def test_identity_is_clean(self):
        g = _golden()
        assert shardaudit.diff_golden(copy.deepcopy(g), g) == []

    def test_spec_mutation_is_drift(self):
        g = _golden()
        mutated = copy.deepcopy(g)
        grp = next(iter(mutated["steps"]["train"]["in"].values()))
        grp["specs"] = ["P('data', None)"]
        drift = shardaudit.diff_golden(mutated, g)
        assert drift and any("specs" in d for d in drift)

    def test_vanished_group_is_drift(self):
        g = _golden()
        mutated = copy.deepcopy(g)
        mutated["steps"]["serve"]["in"].popitem()
        assert shardaudit.diff_golden(mutated, g)

    def test_new_group_is_drift(self):
        g = _golden()
        mutated = copy.deepcopy(g)
        mutated["steps"]["serve"]["out"]["[9]"] = {
            "specs": ["P()"], "leaves": 1, "bytes": 4,
            "max_leaf_bytes": 4}
        assert shardaudit.diff_golden(mutated, g)

    def test_partial_report_compares_only_its_steps(self):
        g = _golden()
        partial = copy.deepcopy(g)
        del partial["steps"]["train"], partial["steps"]["eval"]
        assert shardaudit.diff_golden(partial, g) == []

    def test_declared_replication_change_is_drift(self):
        g = _golden()
        mutated = copy.deepcopy(g)
        mutated["declared"]["corr_fmaps"]["spec"] = "P()"
        mutated["declared"]["corr_fmaps"]["replicated"] = True
        assert shardaudit.diff_golden(mutated, g)

    def test_flagged_groups(self):
        report = {"declared": {
            "corr_fmaps": {"spec": "P()", "total_mb": 128.1,
                           "per_device_mb": 128.1, "replicated": True,
                           "flagged": True},
            "params": {"spec": "P()", "total_mb": 20.0,
                       "per_device_mb": 20.0, "replicated": True,
                       "flagged": False},
        }}
        flagged = shardaudit.flagged_groups(report)
        assert len(flagged) == 1 and "corr_fmaps" in flagged[0]


class TestAuditCLI:
    """Exit-code wiring of scripts/shard_audit.py, with the expensive
    compile stages (both legs — the fsdp one runs by default since the
    axis went live) monkeypatched to replay the shipped goldens — the
    real compiles run in the tier-1 verify command itself."""

    @staticmethod
    def _main():
        spec = importlib.util.spec_from_file_location(
            "_shard_audit_cli", osp.join(REPO, "scripts", "shard_audit.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.main

    @staticmethod
    def _patch_fsdp(monkeypatch):
        # the fsdp AND halo legs answer from their goldens so the CLI
        # tests exercise gate plumbing, not three step compiles
        fsdp_golden = shardaudit.load_golden(shardaudit.FSDP_GOLDEN_PATH)
        monkeypatch.setattr(
            shardaudit, "run_audit_fsdp",
            lambda steps, threshold_mb: copy.deepcopy(fsdp_golden))
        halo_golden = shardaudit.load_golden(shardaudit.HALO_GOLDEN_PATH)
        monkeypatch.setattr(
            shardaudit, "run_audit_halo",
            lambda steps, threshold_mb: copy.deepcopy(halo_golden))

    def test_clean_report_exits_zero(self, monkeypatch):
        main = self._main()
        self._patch_fsdp(monkeypatch)
        monkeypatch.setattr(shardaudit, "run_audit",
                            lambda steps, threshold_mb: copy.deepcopy(
                                _golden()))
        assert main([]) == 0

    def test_spec_drift_exits_nonzero(self, monkeypatch, capsys):
        main = self._main()
        self._patch_fsdp(monkeypatch)

        def mutated(steps, threshold_mb):
            r = copy.deepcopy(_golden())
            grp = next(iter(r["steps"]["train"]["in"].values()))
            grp["specs"] = ["P(None, 'seq')"]
            return r

        monkeypatch.setattr(shardaudit, "run_audit", mutated)
        assert main([]) == 1
        assert "DRIFT" in capsys.readouterr().out

    def test_flagged_replication_exits_nonzero(self, monkeypatch):
        main = self._main()
        self._patch_fsdp(monkeypatch)

        def flagged(steps, threshold_mb):
            r = copy.deepcopy(_golden())
            r["declared"]["corr_fmaps"].update(
                spec="P()", replicated=True, flagged=True)
            return r

        monkeypatch.setattr(shardaudit, "run_audit", flagged)
        assert main([]) == 1


# --------------------------------------------------------------------------
# lint gate satellites: --stats + stale-exclude detection
# --------------------------------------------------------------------------


class TestGateHygiene:
    def test_stats_mode(self):
        r = subprocess.run([sys.executable, GATE, "--stats"], cwd=REPO,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "rule" in r.stdout and "baseline-entries" in r.stdout

    def test_stale_exclude_detected(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "live.py").write_text("x = 1\n")
        bl = jaxlint.Baseline(exclude=["pkg/gone.py", "pkg/live.py"])
        _, _, _, stats = jaxlint.lint_tree(str(tmp_path), subdirs=("pkg",),
                                           baseline=bl)
        assert stats["stale_excludes"] == ["pkg/gone.py"]

    def test_shipped_baseline_has_no_stale_excludes(self):
        bl = jaxlint.Baseline.load(osp.join(
            REPO, "dexiraft_tpu", "analysis", "baseline.json"))
        _, _, _, stats = jaxlint.lint_tree(REPO, baseline=bl)
        assert stats["stale_excludes"] == []
        assert stats["missing_scope"] == []

    def test_missing_scope_file_detected(self, tmp_path):
        """A vanished explicit .py scope entry must surface, not
        silently shrink the gate's coverage."""
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "live.py").write_text("x = 1\n")
        (tmp_path / "entry.py").write_text("y = 2\n")
        _, _, _, stats = jaxlint.lint_tree(
            str(tmp_path), subdirs=("pkg", "entry.py", "gone.py"),
            baseline=jaxlint.Baseline())
        assert stats["missing_scope"] == ["gone.py"]
        assert stats["files"] == 2
