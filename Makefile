PYTHONPATH := src
PY := PYTHONPATH=$(PYTHONPATH) python

.PHONY: test test-fast serve-demo cache-demo obs-demo degraded-demo scrub-demo

# Tier-1 verify: the whole suite, stop on first failure.
test:
	$(PY) -m pytest -x -q

# Skip the slow system/checkpoint suites during iteration.
test-fast:
	$(PY) -m pytest -x -q --ignore=tests/test_system.py --ignore=tests/test_checkpoint.py

# Checkpoint-traffic-under-serving demo: many training jobs stream saves
# through the async block service while latency-class reads run alongside;
# prints the per-tenant QoS-vs-FIFO tail comparison.
serve-demo:
	$(PY) -m repro.launch.serve --storage-sim --policy both

# Warm-cache degraded-read demo: the ZNS cache tier absorbing the hot set
# after a drive failure; prints the warm-vs-cold p50/p99 comparison.
cache-demo:
	$(PY) examples/warm_cache_degraded.py

# Observability demo: checkpoint-under-serving with span tracing, the
# metrics sampler, and the SLO admission controller; writes a
# Perfetto-loadable out/trace.json plus out/metrics.json (schema-validated)
# and prints the static-vs-SLO serving-p99 comparison.
obs-demo:
	$(PY) examples/trace_and_metrics.py

# Always-writable degraded-array demo: fault injection kills a drive
# mid-write-stream, survivor-width stripe groups keep the array writable,
# and the paced rebuild re-widens them; prints the p50/p99 comparison and
# verifies the data round trip.
degraded-demo:
	$(PY) examples/degraded_writes.py

# End-to-end integrity demo: a probabilistic media-fault mix (bit rot,
# torn/misdirected writes, unreadable sectors) lands under a live write
# stream; the paced scrub actor detects every hit against the per-block
# CRC32C lane and repairs in place; writes out/scrub_metrics.json with
# nonzero integrity/blocks_repaired (asserted, and checked again by CI).
scrub-demo:
	$(PY) examples/scrub_repair.py
