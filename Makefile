# Top-level convenience targets (the reference's Makefile/CI entrypoints
# role — see tests/ and native/ for the real work).

# The drills below run at CPU sizes: they ask for the CPU themselves.
# Nothing in tools/ or bench.py picks it by default — those run on
# whatever device JAX finds (`make bench`, `make chip-smoke`).
CPU := JAX_PLATFORMS=cpu

all: native

native:
	$(MAKE) -C native

test: native check
	$(MAKE) -C native test
	$(CPU) python -m pytest tests/ -q
	$(CPU) python tools/wire_report.py
	$(CPU) python tools/memory_report.py
	$(CPU) python tools/loadgen.py
	$(CPU) python tools/dr_drill.py
	$(MAKE) kernels

test-fast: check
	$(CPU) python -m pytest tests/ -q -x --ignore=tests/test_dist.py

check:
	python -m tools.graftcheck

bench:
	python bench.py

# one process, one chip; `python chip_smoke.py --chips 4` on a 4-chip host
chip-smoke:
	python chip_smoke.py

bench-trend:
	python tools/bench_table.py --trend

efficiency:
	$(CPU) python tools/efficiency_report.py

wire:
	$(CPU) python tools/wire_report.py

# PR-20 capacity ledger: reconciled pool books on a checkpointed fit
# AND a generation-lane serving run, then the synthetic OOM squeeze
memory:
	$(CPU) python tools/memory_report.py

dryrun:
	$(CPU) python __graft_entry__.py

dist-test:
	python tools/launch.py -n 2 python tests/dist/dist_sync_kvstore.py

chaos:
	$(CPU) python -m pytest tests/ -q -m chaos

trace:
	$(CPU) python tools/trace_fit.py

watchdog:
	$(CPU) python tools/watchdog_fit.py

elastic:
	$(CPU) python tools/elastic_fit.py

dr:
	$(CPU) python tools/dr_drill.py

continuous:
	$(CPU) python tools/continuous_fit.py

serve:
	$(CPU) python tools/serve.py --smoke

generate:
	$(CPU) python tools/generate_demo.py

slo:
	$(CPU) python tools/slo_report.py

fairness:
	$(CPU) python tools/loadgen.py

# kernels: the full parity grid (exit nonzero on any
# mismatch), then the BENCH_KERNELS=1 lane (which re-gates on the quick
# grid and measures the optimizer-tree CPU win)
kernels:
	$(CPU) python -m mxnet_tpu.ops.fused.parity
	$(CPU) BENCH_KERNELS=1 python bench.py

clean:
	$(MAKE) -C native clean

.PHONY: all native test test-fast check bench chip-smoke bench-trend efficiency \
	wire memory dryrun dist-test chaos trace watchdog elastic dr continuous \
	serve generate slo fairness kernels clean
