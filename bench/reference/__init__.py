"""The benchmark's yardstick: data generators and plain reference semantics.

Nothing here imports the program. The graph and traffic generators are
copies of the paper-reproduction generators (``repro.graphs.generators``,
``repro.core.traffic``, ``repro.core.dynamism``), kept here so that a change
to the program cannot change what the benchmark feeds it;
``bench/tests/test_bench_reference.py`` shows that the copies still agree
with the program's originals at a small scale.
"""
