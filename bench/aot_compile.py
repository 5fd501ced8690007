#!/usr/bin/env python3
"""Compile a cell's serving executables for a described TPU v5e, no chip.

  JAX_PLATFORMS=cpu python3 bench/aot_compile.py --workload qwen3_30b_a3b.chat

Builds the program's HMM and IMM for the cell's configuration and serving
shape on a described ``v5e:2x2`` topology and AOT-compiles the executables
a run would load (decode, and the chunk-prefill or monolithic prefill
buckets).  The TPU compiler refuses what the chip would: kernel block
shapes, fast memory, a program that does not fit.  Prints each
executable's memory analysis and whether it holds the ``paged_gmm`` and
``paged_attention`` kernels.  A compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import os
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from harness import spec
    from repro.core.hmm import HMM
    from repro.core.imm import IMM
    from repro.core.topology import ElasticConfig
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    conf, serving = cell.config, cell.config["serving"]
    mcfg = cell.arch.model_config(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    ops.on_cpu = lambda: False       # the described chips are not the CPU
    L = mcfg.num_layers - mcfg.first_k_dense
    hmm = HMM(mcfg, 1, batch_per_replica=serving["batch_per_replica"],
              max_len=serving["max_len"], all_devices=list(topo.devices),
              kv_mode=serving["kv_mode"], kv_block_size=serving["kv_block"],
              expert_mode="pooled",
              expert_pool_pages=L * mcfg.num_experts, staging="overlap")
    imm = IMM(mcfg, hmm, batch_per_replica=serving["batch_per_replica"],
              max_len=serving["max_len"],
              prefill_buckets=tuple(serving["prefill_buckets"]),
              prefill_chunk=serving["prefill_chunk"])
    inst = imm.preinitialize(ElasticConfig(
        dp=cell.chips, tp=1, devices=tuple(range(cell.chips))))
    for name, exe in inst.compiled.items():
        m = exe.memory_analysis()
        kernels = sorted(set(re.findall(
            r"%(paged_attention|paged_gmm)[.\d]* = ", exe.as_text())))
        print(f"{name}: compiled in {inst.compile_times[name]:.1f} s; "
              f"arguments {m.argument_size_in_bytes / 2**30:.2f} GiB, "
              f"temporaries {m.temp_size_in_bytes / 2**30:.2f} GiB, "
              f"outputs {m.output_size_in_bytes / 2**30:.2f} GiB; "
              f"kernels {kernels}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
