"""The four-chip deployment, cut to a size the CPU runs in seconds, for
the benchmark's tests' scratch roots (``tests.support.make_tiny_root``
cuts every configuration that ``BENCHMARK.json`` names)."""
from chipbench.tests import support

support.TINY.setdefault("robertson_mesh_x4",
                        {"ngroups": 1024, "lanes_per_call": 128})
