"""The benchmark: TPC-H statements over POST /v1/statement on the chip.

Everything the yardstick needs lives in this directory; `BENCHMARK.json` at
the root names the cells. See README.md here.
"""
