# Tier-1 verification + smoke benchmarks (CPU, Pallas interpret mode).
PY ?= python
export PYTHONPATH := src:$(PYTHONPATH)
export JAX_PLATFORMS ?= cpu

.PHONY: test test-kernels test-faultplane test-serve test-population \
	test-topology bench-smoke bench-engine bench-roofline bench-serve \
	smoke-example smoke-lm smoke-fault smoke-serve smoke-population \
	smoke-topology docs check-docs

test:
	$(PY) -m pytest -x -q

# the kernel layer as a required job of its own: the Pallas kernels in
# interpret mode against their jnp oracles + the attention-backend knob
# (flash vs reference through the model and federated layers)
test-kernels:
	$(PY) -m pytest -q tests/test_kernels.py tests/test_attention_backend.py

# the fault plane as a required job of its own: churn/blackout/gate
# units + the bitwise crash-resume suite (including the SIGKILL chaos
# subprocess test)
test-faultplane:
	$(PY) -m pytest -q tests/test_faultplane.py tests/test_crash_resume.py

# the serving plane as a required job of its own: prefill/decode bitwise
# parity vs the training forward, continuous-batching conservation +
# slot recycling, and spec-hash-addressed checkpoint loading
test-serve:
	$(PY) -m pytest -q tests/test_serve.py

# the population plane as a required job of its own: stacked-vs-streaming
# bitwise parity, the FLGo-style availability/responsiveness/completion
# process grammars, flat-memory scaling, and the cross-plane composition
# suites live in tests/test_population.py
test-population:
	$(PY) -m pytest -q tests/test_population.py

# the topology plane as a required job of its own: the degenerate
# bitwise contract (1-silo/1-edge zero-delay topology == flat FedAT),
# per-link delay/codec/byte accounting, delayed-gradient compensation,
# and the topology x faults x population cross-plane suites
test-topology:
	$(PY) -m pytest -q tests/test_topology.py

# regenerate the introspected ExperimentSpec reference (docs/SPEC.md)
docs:
	$(PY) scripts/gen_spec_docs.py

# CI docs gate: docs/SPEC.md must match the dataclasses (no drift) and
# every intra-repo markdown link must resolve
check-docs:
	$(PY) scripts/gen_spec_docs.py --check
	$(PY) scripts/check_links.py

# spec-API quickstart as an executable smoke test (CI runs this)
smoke-example:
	$(PY) examples/quickstart.py --updates 12

# 2-round federated tiny_lm through the CLI: exercises the model
# registry path (data.model) end-to-end on every push (CI runs this)
smoke-lm:
	$(PY) -m repro.api.cli \
	    --set data.model=tiny_lm --set data.n_clients=8 \
	    --set data.samples_per_client=12 --set tiers.n_tiers=2 \
	    --set tiers.clients_per_round=2 --set tiers.n_unstable=0 \
	    --set engine.local_epochs=1 --set engine.total_updates=2 \
	    --set engine.eval_every=2

# 2-round run under the full fault plane through the CLI: client churn,
# a poisoned uplink behind the validation gate, and a tier blackout
# (CI runs this on every push)
smoke-fault:
	$(PY) -m repro.api.cli \
	    --set data.n_clients=8 --set data.samples_per_client=12 \
	    --set data.image_hw=8 --set tiers.n_tiers=2 \
	    --set tiers.clients_per_round=2 --set tiers.n_unstable=0 \
	    --set engine.local_epochs=1 --set engine.total_updates=2 \
	    --set engine.eval_every=2 \
	    --set faults.churn_rate=0.5 --set 'faults.churn_window=[1,40]' \
	    --set faults.churn_downtime=10 --set faults.nan_rate=0.5 \
	    --set faults.blackouts=1 --set 'faults.blackout_window=[1,20]' \
	    --set faults.blackout_duration=10

# train -> checkpoint -> serve through the CLI: 2 federated tiny_lm
# rounds with --checkpoint-dir, then the `serve` subcommand resolves the
# directory by spec hash and decodes a Poisson request stream (CI runs
# this on every push)
smoke-serve:
	rm -rf /tmp/smoke_serve_ckpt
	$(PY) -m repro.api.cli \
	    --set data.model=tiny_lm --set data.n_clients=8 \
	    --set data.samples_per_client=12 --set tiers.n_tiers=2 \
	    --set tiers.clients_per_round=2 --set tiers.n_unstable=0 \
	    --set engine.local_epochs=1 --set engine.total_updates=2 \
	    --set engine.eval_every=2 --checkpoint-dir /tmp/smoke_serve_ckpt
	$(PY) -m repro.api.cli serve --resume-from /tmp/smoke_serve_ckpt \
	    --requests 6 --slots 3 --prompt-len 12 --max-new 6 --rate 25

# 2 federated rounds over a 100k-client population through the CLI:
# streaming plane, stochastic availability, flat device memory — proves
# the population spec section end-to-end on every push (CI runs this)
smoke-population:
	$(PY) -m repro.api.cli \
	    --set data.n_clients=100000 --set data.samples_per_client=12 \
	    --set data.image_hw=8 --set tiers.n_tiers=5 \
	    --set tiers.clients_per_round=8 --set tiers.n_unstable=1000 \
	    --set engine.local_epochs=1 --set engine.total_updates=2 \
	    --set engine.eval_every=2 \
	    --set population.plane=streaming \
	    --set population.availability=bernoulli:0.9:20 \
	    --set population.eval_clients=32

# 2-region hierarchical federation through the CLI: 2 silos x 2 edges,
# WAN delay bands on every link class, a lossy silo->global WAN codec,
# and delayed-gradient compensation on the stale silo path (CI runs
# this on every push)
smoke-topology:
	$(PY) -m repro.api.cli \
	    --set data.n_clients=16 --set data.samples_per_client=12 \
	    --set data.image_hw=8 --set tiers.n_tiers=1 \
	    --set tiers.clients_per_round=4 --set tiers.n_unstable=0 \
	    --set engine.local_epochs=1 --set engine.total_updates=4 \
	    --set engine.eval_every=2 \
	    --set topology.n_silos=2 --set topology.edges_per_silo=2 \
	    --set 'topology.delay.client_edge=[0.5,1.5]' \
	    --set 'topology.delay.edge_silo=[1,3]' \
	    --set 'topology.delay.silo_global=[2,6]' \
	    --set topology.codec.silo_global=quantize8 \
	    --set topology.compensation=0.5 --set topology.silo_skew=0.5

bench-smoke:
	$(PY) -m benchmarks.run codec codec_e2e kernels

# kernel roofline: per-kernel achieved FLOP/s vs the machine roof
# (calibrated in place off-TPU), merged into BENCH_engine.json next to
# the engine rows.  SMOKE=1 shrinks sizes/reps (the CI push workflow
# runs `make bench-roofline SMOKE=1`).
bench-roofline:
	$(PY) -m benchmarks.run roofline $(if $(SMOKE),--smoke) \
	    --json BENCH_engine.json

# engine hot-path throughput (events/sec per strategy) + the scale axis
# (512-client scenario on one device) + the federated-LM path
# (tiny_lm with/without the polyline codec) + the fault-plane
# degradation curve (0/5%/20% fault pressure) + the population plane
# (streaming rounds at 1k/100k/1M clients, flat-memory pin) + the
# topology plane (flat vs hierarchical ev/s, per-link-class wire bytes,
# compensation vs staleness, degenerate bitwise pin re-checked) +
# machine-readable JSON for cross-PR perf tracking
# (the client-sharded row, `benchmarks.run engine_scaled engine_sharded`,
# needs a process that sees at least 2 devices)
bench-engine:
	$(PY) -m benchmarks.run engine engine_scaled engine_lm \
	    engine_faults engine_population \
	    engine_topology $(if $(SMOKE),--smoke) --json BENCH_engine.json

# serving-plane latency under open-loop Poisson load, from spec-hash-
# verified federated checkpoints (train -> checkpoint -> load -> serve):
# p50/p95/p99 latency + TTFT + tok/s per load level into
# BENCH_serve.json.  SMOKE=1 shrinks rounds/requests (the CI push
# workflow runs `make bench-serve SMOKE=1`).
bench-serve:
	$(PY) -m benchmarks.serve_bench $(if $(SMOKE),--smoke) \
	    --json BENCH_serve.json
