"""The benchmark's command: one cell, one seed, one run, one JSON line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs training through the entry points a user calls: `ray_tpu.init()` ->
`JaxTrainer(loop, ScalingConfig(use_tpu=True, ...)).fit()` ->
`create_train_state` / `make_train_step`. The cell is an entry of
`BENCHMARK.json`; its configuration, traffic mix, loop and per-layer metrics
are files under `benchmark/`, found by name. Fails, with no result line,
where there is no TPU or fewer chips than the cell asks for.
`--rehearse-cpu` walks the same path with the nano configuration on the CPU.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark.harness import procs

    t_start = procs.process_start_wall()
    from benchmark.harness.driver import main

    sys.exit(main(sys.argv[1:], t_start))
