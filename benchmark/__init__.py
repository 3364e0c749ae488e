"""harmony-tpu's benchmark: cells named in BENCHMARK.json, run by run.py."""
