# Convenience targets (the Python package needs no build; the native
# library compiles itself on first use into the source-hash cache — the
# `native` target just runs that one real build path eagerly).

.PHONY: all native lint lint-ir lint-threads lint-exchange lint-programs lint-memory mem-smoke test verify bench bench-gate obs-smoke serve-smoke serve-obs serve-bench serve-slo snapshot-smoke serve-sharded-smoke gas-smoke gas-sharded-smoke exchange-smoke prof-smoke ledger-smoke tune-smoke race-stress chaos-stress clean

all: native

native:
	python -c "from lux_tpu.native.build import load_library; load_library(); print('native library ready')"

lint:
	python tools/luxlint.py

lint-ir:
	python tools/luxlint.py --ir

# Concurrency tier: thread-shared state vs lock guards, the cross-file
# lock-order graph, blocking-under-lock, unjoined threads, publish
# discipline (LUX301-305).
lint-threads:
	python tools/luxlint.py --threads

# Exchange tier: ExchangePlan structure/coverage/profitability proofs
# plus the overlap, sentinel-annihilator, byte-accounting, and
# frontier-coverage dataflow rules over every full+compact+frontier
# sharded registry target (LUX401-407).
lint-exchange:
	python tools/luxlint.py --exchange

# Program-contract tier: prove each registered program's combiner
# identity/exactness, push/pull duality, frontier annihilation, and
# monotone convergence (LUX601-606), assert parity between the derived
# gascap.v1 capability matrix and the committed artifact, and show a
# seeded broken program is caught — all inside a 2s wall budget.
lint-programs:
	env JAX_PLATFORMS=cpu python tools/gasck_smoke.py

# Memory tier: donation-aware buffer-liveness walk over every traced
# registry target deriving per-device peak live bytes and the closed
# footprint model f(nv, ne, P, K, exchange_mode), checked against the
# committed content-addressed memcap.v1 artifact (LUX701-706).
lint-memory:
	env JAX_PLATFORMS=cpu python tools/luxlint.py --memory

# Memory-tier acceptance: registry priced clean inside the 2s proof
# budget, derived memcap.v1 id equal to the committed artifact, the
# seeded LUX702 donation-leak fixture caught, footprint-LRU pool
# eviction with zero warm-hit recompiles, and an over-budget engine
# build shed at the HTTP front end with a typed 503 + Retry-After.
mem-smoke:
	env JAX_PLATFORMS=cpu python tools/memck_smoke.py

test:
	python -m pytest tests/ -q

verify: lint lint-ir lint-threads lint-exchange lint-programs lint-memory mem-smoke test serve-obs snapshot-smoke serve-sharded-smoke gas-smoke gas-sharded-smoke exchange-smoke prof-smoke ledger-smoke tune-smoke race-stress chaos-stress bench-gate

bench:
	python bench.py

# Regression gate: a fast tiny-graph bench round (CPU-safe, <30s) emits
# bench_gate.v1 JSON and ratchets against the newest BENCH_r0N.json
# baseline with per-metric tolerances (LUX_BENCH_GATE_TOL).
bench-gate:
	env JAX_PLATFORMS=cpu python tools/bench_gate.py --fast

obs-smoke:
	python tools/obs_smoke.py

serve-smoke:
	python tools/serve_smoke.py

# serve-smoke including the observability acceptance: one trace-id
# across admission->batch->engine->cache, Prometheus /metrics, /statusz,
# and a flight.v1 postmortem on an injected deadline miss.
serve-obs:
	python tools/serve_smoke.py

# Dynamic-graph acceptance: hot-swap under in-flight traffic, FIFO drain
# barrier, incremental cache refresh, zero recompiles, one swap trace-id.
snapshot-smoke:
	python tools/snapshot_smoke.py

# Multi-chip serving acceptance: sharded engines on a virtual 8-way CPU
# mesh behind the warm pool — bitwise parity vs single-chip, hot-swap of
# the whole engine mesh under load, zero recompiles, /statusz mesh view.
serve-sharded-smoke:
	python tools/serve_sharded_smoke.py

# GAS subsystem acceptance: every registry app served over HTTP with
# host-oracle agreement, >= 1 adaptive mid-run direction switch on the
# single-lane BFS, zero recompiles, /statusz direction-split block.
gas-smoke:
	python tools/gas_smoke.py

# Sharded GAS acceptance (LUX_EXCHANGE=frontier): every registry app
# answered from a 2x4 virtual mesh bitwise against the host oracles,
# >= 1 adaptive direction switch on the single-lane BFS, an empty
# mesh-fallback surface (counter at zero), zero recompiles across
# switches and frontier downgrades, and the frontier-vs-compact
# exchange-byte budget report.
gas-sharded-smoke:
	python tools/gas_sharded_smoke.py

# Compacted-exchange acceptance (LUX_EXCHANGE=compact): bitwise parity
# full-vs-compact for SSSP + PageRank on a 2x4 virtual mesh, >= 5x
# exchange-byte drop on the halo locality graph, zero recompiles, and
# a phase-fenced exchange_hidden_frac report.
exchange-smoke:
	python tools/exchange_smoke.py

# Profiler acceptance (obs/prof.py): a REAL jax.profiler capture around
# warm sharded steps parsed by the stdlib profile.v1 parser — both
# region tags classified, interval math consistent, zero recompiles
# with regions armed, /profilez guarded (403/429/200) under a
# concurrent burst, /statusz budget labeling.
prof-smoke:
	python tools/prof_smoke.py

# Observability-ledger acceptance: two-tenant warm HTTP burst with
# LUX_LEDGER_DIR armed — X-Lux-Cost on every reply, /costz totals equal
# to the lux_query_cost_* metric values, crc-clean runrec.v1 records
# whose config_hash reproduces, a CLEAN lux_doctor verdict, zero
# recompiles.
ledger-smoke:
	env JAX_PLATFORMS=cpu python tools/ledger_smoke.py

# Auto-tuner acceptance: seeded synthetic where a known-better
# non-default exchange mode must be selected, real probe records in
# the ledger, luxlint --tune clean over the artifacts, serving warmup
# applying the tuned config with zero recompiles and bitwise-identical
# BFS results, lux_doctor --tuned attribution.
tune-smoke:
	env JAX_PLATFORMS=cpu python tools/tune_smoke.py

# Concurrency acceptance: burst + mid-burst swap + forced compaction
# with LockWatch armed — zero lock-order inversions, zero failed
# queries, zero recompiles, bounded hold-time p99.
race-stress:
	python tools/race_stress.py

# Robustness acceptance: burst with every fault point armed (all
# requests terminal), breaker open->half_open->closed lifecycle, and an
# injected crash recovered bitwise from the WAL with zero steady-state
# recompiles. The WAL torn-write unit tests run under `test`.
chaos-stress:
	python tools/chaos_stress.py

serve-bench:
	python tools/serve_bench.py --scale 12 --workers 16 --duration 10

# SLO gate: bench -> serve_bench.v1 JSON -> compare against the pinned
# baseline (written on first run; commit bench/serve_slo_baseline.json).
serve-slo:
	python tools/serve_bench.py --scale 10 --workers 8 --duration 5 \
		--json-out /tmp/lux_serve_bench.json
	python tools/slo_check.py --input /tmp/lux_serve_bench.json \
		--baseline bench/serve_slo_baseline.json

clean:
	rm -rf build ~/.cache/lux_tpu_native
