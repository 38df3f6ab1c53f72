"""``python3 -m benchmark --workload <cell> --seed <n> --seconds <s> --trace <0|1>``:
one run of one cell (see ``benchmark/harness.py``)."""
import time

T_START = time.monotonic()

if __name__ == "__main__":
    from benchmark.harness import main

    main(t_start=T_START)
