"""The benchmark's general code: it finds each configuration, traffic mix
and metric by the name ``BENCHMARK.json`` gives it, drives the serving
engine through a measured window, reduces the profiler trace and decides
whether what was served is correct.  Nothing here names a particular
configuration, mix or metric."""
