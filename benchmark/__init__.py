"""The repo's benchmark: the yardstick later PRs are held to (see README.md here).

Nothing in this package is imported by the program, and the program's PRs may
not edit it. One run is ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.
"""
