"""The on-chip benchmark of horovod-tpu: the yardstick every later PR is held
to. `BENCHMARK.json` at the root of the repo names the cells; everything a
cell is made of is found here by those names (see `harness/spec.py`).

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
