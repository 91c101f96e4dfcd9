"""The on-chip serving benchmark.

One run of one cell: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  Everything that belongs to one model
configuration, one traffic mix, one cell or one metric lives in a file
of its own, found by name:

* ``configs/<config>.json``   model sizes as run, the source, the cut;
* ``references/<name>.py``   the plain reference a configuration names:
  one family's sizes, weights, forward pass, flop count and the
  program's layout of its weights;
* ``traffic/<mix>.json``      a traffic mix: parameters of a generator;
* ``traffic/<generator>.py``  a seeded open-loop generator;
* ``workloads/<cell>.json``   configuration + mix + rate + engine sizes;
* ``metrics/<metric>.py``     one reader per metric (the part of the
  name before its first ``.``), which returns a number or None.

The yardstick (traffic, trace reduction, peaks, flop and byte counts,
the plain reference and the comparison that decides ``correct``) lives
here; from the program the benchmark takes only the serving engine, the
model it builds and the names of its jitted steps and kernel.
"""
