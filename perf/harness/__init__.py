"""The benchmark's harness: everything here is general; whatever belongs to
one configuration, traffic mix or per-layer metric is a file of its own,
found by the name ``BENCHMARK.json`` gives it (see ``perf/README.md``)."""
