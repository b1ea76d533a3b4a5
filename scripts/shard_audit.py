"""CI shard-audit gate: compile the train/eval/serve steps on a forced
8-virtual-device host mesh, resolve every input/output leaf's sharding,
and diff against the checked-in golden — exit nonzero on any drift.

The static companion to scripts/lint_gate.py: lint proves specs are
DRAWN from the canonical layout (parallel/layout.py, jaxlint JL010+);
this proves what the compiled executables actually DO with them, and
that nothing big resolves fully replicated (the ~200 MB correlation
volume being the canary). Runs on CPU — GSPMD partitioning is
platform-independent, so the resolved specs here are the pod's specs.
Wired into the tier-1 verify command right after lint_gate.py
(ROADMAP.md).

Usage:
  python scripts/shard_audit.py                  # gate: diff vs ALL
                                                 # goldens (incl. the
                                                 # fsdp and halo legs)
  python scripts/shard_audit.py --write-golden   # regenerate all three
                                                 # (review the diff in
                                                 # the PR!)
  python scripts/shard_audit.py --steps serve    # partial (faster) audit
  python scripts/shard_audit.py --steps train_fsdp  # fsdp leg only
  python scripts/shard_audit.py --steps train_halo  # halo leg only
  python scripts/shard_audit.py --json           # dump the full report

Exit codes: 0 clean, 1 drift or a flagged replicated group.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# The audit runs on virtual CPU devices whatever the host holds: force
# the platform and the device count BEFORE jax's backend initializes.
_N_DEVICES = 8
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={_N_DEVICES}")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("shard_audit")
    ap.add_argument("--steps",
                    default="train,eval,serve,serve_encode,serve_refine,"
                            "train_fsdp,train_halo",
                    help="comma-separated subset of train,eval,serve,"
                         "serve_encode,serve_refine,train_fsdp,"
                         "train_halo (partial runs diff only their "
                         "sections; train_fsdp diffs the fsdp golden, "
                         "train_halo the halo one — the "
                         "compute_sharding='halo' step; serve_encode/"
                         "serve_refine are the split-model streaming "
                         "signatures)")
    ap.add_argument("--golden", default=None,
                    help="golden path (default: "
                         "dexiraft_tpu/analysis/layout_golden.json)")
    ap.add_argument("--fsdp-golden", default=None,
                    help="fsdp golden path (default: dexiraft_tpu/"
                         "analysis/layout_golden_fsdp.json)")
    ap.add_argument("--halo-golden", default=None,
                    help="halo golden path (default: dexiraft_tpu/"
                         "analysis/layout_golden_halo.json)")
    ap.add_argument("--write-golden", action="store_true",
                    help="regenerate ALL goldens from this run (always "
                         "audits ALL steps)")
    ap.add_argument("--threshold-mb", type=float, default=None,
                    help="replicated-array size tripwire (default 64)")
    ap.add_argument("--json", action="store_true",
                    help="print the full report JSON")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")

    from dexiraft_tpu.analysis import shardaudit

    golden_path = args.golden or shardaudit.GOLDEN_PATH
    fsdp_golden_path = args.fsdp_golden or shardaudit.FSDP_GOLDEN_PATH
    halo_golden_path = args.halo_golden or shardaudit.HALO_GOLDEN_PATH
    threshold = (args.threshold_mb if args.threshold_mb is not None
                 else shardaudit.DEFAULT_THRESHOLD_MB)
    steps = [s for s in args.steps.split(",") if s]
    known = (set(shardaudit.STEP_AUDITS) | set(shardaudit.FSDP_STEP_AUDITS)
             | set(shardaudit.HALO_STEP_AUDITS))
    unknown = set(steps) - known
    if unknown:
        ap.error(f"unknown steps {sorted(unknown)}; "
                 f"choose from {sorted(known)}")
    if args.write_golden:
        steps = sorted(known)
    main_steps = [s for s in steps if s in shardaudit.STEP_AUDITS]
    fsdp_steps = [s for s in steps if s in shardaudit.FSDP_STEP_AUDITS]
    halo_steps = [s for s in steps if s in shardaudit.HALO_STEP_AUDITS]

    # (report, golden path, label) per golden file in play — the fsdp
    # leg diffs its own golden so the data x seq one never drifts when
    # only the fsdp layout changes (and vice versa)
    legs = []
    if main_steps or args.write_golden:
        legs.append((shardaudit.run_audit(main_steps,
                                          threshold_mb=threshold),
                     golden_path, "main"))
    if fsdp_steps:
        legs.append((shardaudit.run_audit_fsdp(fsdp_steps,
                                               threshold_mb=threshold),
                     fsdp_golden_path, "fsdp"))
    if halo_steps:
        legs.append((shardaudit.run_audit_halo(halo_steps,
                                               threshold_mb=threshold),
                     halo_golden_path, "halo"))

    if args.json:
        print(json.dumps({label: rep for rep, _, label in legs},
                         indent=1, sort_keys=True))

    flagged = []
    for rep, _, label in legs:
        for line in shardaudit.flagged_groups(rep):
            flagged.append(f"[{label}] {line}")
    for line in flagged:
        print(f"shard audit: FLAGGED {line}")

    if args.write_golden:
        if flagged:
            print("shard audit: refusing to write a golden with flagged "
                  "replicated groups — fix the layout first")
            return 1
        for rep, path, label in legs:
            shardaudit.write_golden(rep, path)
            print(f"shard audit: wrote {path} "
                  f"(hash {shardaudit.golden_hash(path)[:12]})")
        return 0

    drift = []
    hashes = []
    for rep, path, label in legs:
        try:
            golden = shardaudit.load_golden(path)
        except FileNotFoundError:
            print(f"shard audit: no golden at {path} — bootstrap with "
                  f"--write-golden")
            return 1
        drift += [f"[{label}] {d}"
                  for d in shardaudit.diff_golden(rep, golden)]
        hashes.append(shardaudit.golden_hash(path)[:12])
    for line in drift:
        print(f"shard audit: DRIFT {line}")
    ok = not drift and not flagged
    print(f"shard audit: {len(steps)} step(s) "
          f"({','.join(steps)}), {len(drift)} drift line(s), "
          f"{len(flagged)} flagged group(s), golden {'+'.join(hashes)}"
          f"{'' if ok else ' — FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
