"""The multi-phase suite that lived here is gone (PR 45; nothing read it): the
benchmark is ``python3 benchmark/run.py`` (``BENCHMARK.json``,
``benchmark/README.md``).  This stub stays only because the benchmark's own
validator test (``tests/benchmark/test_benchmark_spec.py``, which only a
``benchmark`` PR may edit) uses this NAME as its example of a command file
outside the benchmark's paths, and checks that the file exists."""
import sys

sys.exit("bench.py is gone: run python3 benchmark/run.py --workload <cell> "
         "--seed N --seconds 40 (benchmark/README.md)")
