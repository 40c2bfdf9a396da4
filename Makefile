# Tier-1 verification + dev conveniences.  Tests run on the CPU
# (JAX_PLATFORMS=cpu; Pallas kernels in explicit interpret mode, and
# compiles for a described v5e in tests/test_tpu_compile.py).  The chip is
# reached through `python chip_smoke.py [--four-chips]`.

PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export JAX_PLATFORMS ?= cpu

.PHONY: verify verify-ci verify-docs test dev-deps sim-check fuzz bench \
        bench-planner bench-costmodel bench-sim bench-robustness bench-ft \
        bench-adaptive bench-fig6b bench-sweep bench-obs example-sim

verify:
	$(PYTHON) -m pytest -x -q

verify-ci: verify

# modules whose docstrings carry runnable >>> examples (the ISSUE 2
# docstring pass); --doctest-modules is the package-aware `python -m
# doctest` (relative imports need the package context)
DOCTEST_MODULES := \
  src/repro/sim/engine.py src/repro/sim/events.py src/repro/sim/policies.py \
  src/repro/sim/scenario.py src/repro/sim/validate.py \
  src/repro/sim/advance.py src/repro/sim/fuzz.py src/repro/sim/robustness.py \
  src/repro/core/bcd.py src/repro/core/cost_model.py \
  src/repro/core/microbatch.py \
  src/repro/ft/policy.py src/repro/ft/adaptive.py \
  src/repro/pipeline/schedule.py

# docs job: doctests over the documented APIs + the docs/*.md anchor/link
# check + export hygiene
verify-docs:
	$(PYTHON) -m pytest -q --doctest-modules \
	  $(DOCTEST_MODULES) tests/test_docs.py tests/test_exports.py

test:
	$(PYTHON) -m pytest -q

dev-deps:
	$(PYTHON) -m pip install -r requirements-dev.txt

# fast standalone consistency check: event engine vs Eqs. (12)-(14)
sim-check:
	$(PYTHON) -m pytest -q tests/test_sim.py

# fixed-seed differential fuzz campaign + CVaR selection smoke: shrunk
# parity breakers land in tests/corpus/, summary CSVs in results/bench/
fuzz:
	$(PYTHON) -m benchmarks.bench_robustness --smoke

# planner scaling grid + the ISSUE-3 acceptance instance; rewrites the
# repo-root BENCH_planner.json perf-trajectory file
bench-planner:
	$(PYTHON) -m benchmarks.bench_planner

# closed-form vs sim-refined BCD on reentrant/memory-starved instances;
# rewrites the repo-root BENCH_costmodel.json trajectory file
bench-costmodel:
	$(PYTHON) -m benchmarks.bench_costmodel

# trace-aware engine scaling + sim-in-the-loop solve overhead;
# rewrites the repo-root BENCH_sim.json trajectory file
bench-sim:
	$(PYTHON) -m benchmarks.bench_sim

# 500-case fuzz parity campaign + robust-vs-nominal plan selection;
# rewrites the repo-root BENCH_robustness.json trajectory file
bench-robustness:
	$(PYTHON) -m benchmarks.bench_robustness

# replan-policy zoo on the fixed-seed flap corpus + the Periodic-cadence vs
# Gauss-Markov-drift frontier; rewrites the repo-root BENCH_ft.json file
bench-ft:
	$(PYTHON) -m benchmarks.bench_ft_policy

# adaptive-cadence vs fixed-cadence regimes, tail-sized admission under
# fuzzed memory pressure, and the successive-halving policy tuner;
# rewrites the repo-root BENCH_adaptive.json trajectory file
bench-adaptive:
	$(PYTHON) -m benchmarks.bench_adaptive

bench: bench-planner bench-costmodel bench-sim bench-robustness bench-ft \
       bench-adaptive bench-fig6b bench-sweep bench-obs

# telemetry overhead on the 10k-micro-batch acceptance chain: asserts the
# enabled-mode slowdown stays < 5% and disabled mode is a true no-op
bench-obs:
	$(PYTHON) -m benchmarks.bench_obs

bench-fig6b:
	$(PYTHON) -m benchmarks.fig6b_traces

# topology x fluctuation x admission-policy sweep + engine-scaling grid
bench-sweep:
	$(PYTHON) -m benchmarks.sweep_grid

example-sim:
	$(PYTHON) examples/simulate_pipeline.py
