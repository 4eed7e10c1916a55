PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: test test-fast test-validate test-multihost coverage lint smoke bench bench-plan bench-gate deps deps-dev

test:           ## tier-1 verify (full suite, fail-fast)
	$(PYTHON) -m pytest -x -q

test-fast:      ## core scheduling + engine + telemetry tests only
	$(PYTHON) -m pytest -x -q tests/test_interfaces.py \
	    tests/test_schedulers.py tests/test_engine.py tests/test_telemetry.py

# REPRO_PLAN_VALIDATE=1 makes the engine cross-check every vectorized plan
# chunk-for-chunk against the generic three-op driver (slow, exhaustive)
test-validate:  ## tier-1 with plan validation on
	REPRO_PLAN_VALIDATE=1 $(PYTHON) -m pytest -x -q

test-multihost: ## multi-host equivalence + replan suite (4 emulated hosts)
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PYTHON) -m pytest -q tests/test_train_multihost.py

coverage:       ## tier-1 under coverage (4 emulated hosts); CI floor 82%
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PYTHON) -m pytest -q --cov=repro --cov-report=term-missing \
	    --cov-report=xml --cov-fail-under=82

lint:           ## ruff over the whole tree (ruff.toml) + docs registry sync
	ruff check .
	$(PYTHON) tools/check_docs.py

smoke:          ## public-API smoke: quickstart + clause-string dry runs (CI job)
	$(PYTHON) examples/quickstart.py
	$(PYTHON) -m repro.launch.serve --arch rwkv6-3b --smoke \
	    --requests 4 --max-concurrency 2 --scheduler "guided,4" --max-new 4
	$(PYTHON) -m repro.launch.serve --arch qwen2.5-3b --smoke \
	    --requests 8 --scheduler "guided,4" --max-new 8 \
	    --num-blocks 24 --block-size 8 --max-concurrency 8 \
	    --decode-steps 4
	$(PYTHON) -m pytest -q tests/test_serve.py
	$(PYTHON) -m repro.launch.train --arch qwen2.5-3b --smoke \
	    --steps 2 --batch 4 --seq-len 64 --scheduler "guided,4"
	$(PYTHON) -m repro.launch.train --arch qwen2.5-3b --smoke \
	    --steps 2 --batch 4 --seq-len 64 --scheduler "guided,4" \
	    --microbatches 2 --fused-microbatches
	REPRO_UDS_MODULES=examples.uds_blocks PYTHONPATH=src:. \
	    $(PYTHON) -m repro.launch.train --arch qwen2.5-3b --smoke \
	    --steps 2 --batch 4 --seq-len 64 --scheduler "uds:blocks,8"
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PYTHON) -m pytest -q tests/test_train_multihost.py
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PYTHON) -m repro.launch.train --arch qwen2.5-3b --smoke \
	    --steps 2 --batch 4 --seq-len 64 --hosts 4 \
	    --straggler-scheduler "wf2"
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PYTHON) -m repro.launch.train --arch qwen2.5-3b --smoke \
	    --steps 2 --batch 8 --seq-len 64 --hosts 4 --microbatches 2 \
	    --scheduler "hier(host=awf, device=guided,4)"
	$(PYTHON) -m repro.launch.serve --arch qwen2.5-3b --smoke \
	    --requests 4 --max-concurrency 2 --scheduler auto --max-new 4
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PYTHON) -m repro.launch.train --arch qwen2.5-3b --smoke \
	    --steps 2 --batch 4 --seq-len 64 --hosts 4 \
	    --straggler-scheduler auto
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PYTHON) -m repro.launch.train --arch qwen2.5-3b --smoke \
	    --steps 4 --batch 8 --seq-len 64 --hosts 4 --elastic \
	    --kill-hosts 2,3 --kill-at 2
	$(PYTHON) -m repro.launch.serve --arch qwen2.5-3b --smoke \
	    --requests 8 --scheduler dynamic --max-new 6 \
	    --num-blocks 48 --block-size 8 --max-concurrency 8 \
	    --kill-rows 3 --kill-at-dispatch 2
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PYTHON) examples/fault_tolerant_train.py

bench:          ## full benchmark harness (CSV stdout, JSON to benchmarks/results/)
	$(PYTHON) benchmarks/run.py

bench-plan:     ## plan-engine speedup + cache-hit acceptance check
	$(PYTHON) benchmarks/plan_engine.py

bench-gate:     ## CI regression gates: write BENCH_*.json, fail on regression
	$(PYTHON) benchmarks/plan_engine.py --json BENCH_plan_engine.json --gate
	$(PYTHON) benchmarks/serve_adapt.py --json BENCH_serve.json --gate
	$(PYTHON) benchmarks/train_straggler.py --json BENCH_train.json --gate
	# elastic_recovery MERGES into the bench records the two lines above
	# overwrite — it must run last
	$(PYTHON) benchmarks/elastic_recovery.py --json-train BENCH_train.json \
	    --json-serve BENCH_serve.json --gate

deps:
	pip install -r requirements.txt

deps-dev:
	pip install -r requirements-dev.txt
