#!/usr/bin/env python3
"""Compile a cell's programs at their real size for a described TPU
topology, with no chip attached (`on-chip-measurement` guide, section 2).

    JAX_PLATFORMS=cpu python3 benchmarks/compile_check.py [--topology v5e:2x2] <workload> ...

For each workload named (all of BENCHMARK.json's when none is): the
cell's step program as the traffic file states it, and the plain
reference program its check runs, are lowered from shapes by the traffic
kind's runner (`compile_for`) and handed to the chip's compiler. What the compiler refuses on the chip (a kernel
Mosaic rejects, a program that does not fit the chip's memory) it refuses
here. Prints one line per program: compile seconds, temporary bytes per
device, whether a `tpu_custom_call` is in the module, and the number of
`all-reduce` ops. A compile that passes is not a chip run: nothing
executes, so this says nothing about results or times.
"""

from __future__ import annotations

import argparse
import os
import os.path as osp
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))


def _report(label: str, lowered) -> None:
    t0 = time.perf_counter()
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    print(f"COMPILED {label}: {dt:.0f} s, temp "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, args "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, tpu_custom_call="
          f"{'tpu_custom_call' in text}, all-reduce ops="
          f"{text.count(' all-reduce(') + text.count(' all-reduce-start(')}",
          flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--topology", default="v5e:2x2")
    p.add_argument("workloads", nargs="*")
    args = p.parse_args(argv)

    import jax
    from jax.experimental import topologies

    from benchmarks import harness

    # such a compile cannot be read back from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    names = args.workloads or [w["name"] for w in
                               harness.load_manifest()["workloads"]]
    for name in names:
        cell = harness.load_cell(name)
        harness.load_runner(cell.traffic["kind"]).compile_for(cell, topo, _report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
