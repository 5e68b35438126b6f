"""The benchmark of keystone_tpu: the yardstick later PRs are held to.

``python -m benchmarks.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` measures one cell of ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix, driver kind, per-layer metric,
plain reference or operation count is a file of its own, found by name;
adding one edits nothing here.
"""
