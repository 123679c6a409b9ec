# Commit gate (VERDICT r2 #4): `make check` must be green before a snapshot.
.PHONY: check check-fast check-device native sanitize sanitize-native sanitize-py metrics-lint lint soak loadgen

check:
	./scripts/check.sh

# Static-analysis half of the gate (check.sh runs it before the pytest
# groups). phantlint is the Python/JAX analog of `make sanitize` below:
# sanitize catches memory bugs in the native C++ runtime at runtime,
# phantlint catches host-sync / dtype-drift / jit-hygiene / lock-discipline
# / metric-name hazards in the ~14k-line Python side at parse time — the
# two together are the whole-codebase analysis surface. Pure ast, no jax:
# the full package lints in ~2s. Intentional hazards carry inline
# `# phantlint: disable=RULE — reason` annotations; anything grandfathered
# lives in scripts/phantlint_baseline.json (currently EMPTY — keep it so).
# scripts/ gets a second pass under the concurrency rules only — soak
# and loadgen spawn threads too, but the JAX-hygiene rules don't apply
# to host-side driver scripts.
lint:
	JAX_PLATFORMS=cpu python scripts/phantlint.py phant_tpu/ \
	  --baseline scripts/phantlint_baseline.json
	JAX_PLATFORMS=cpu python scripts/phantlint.py scripts/ \
	  --rules LOCK,LOCKORDER,LOCKBLOCK,THREADSHARE \
	  --baseline scripts/phantlint_baseline.json

# Quick iteration subset (NOT a substitute for `make check` before commits):
# skips the compile-heavy device-kernel files.
check-fast:
	PHANT_CHECK_DEVICE=0 ./scripts/check.sh -x

# Only the device-kernel files (CI runs this in parallel with check-fast).
# Keep in sync with scripts/check.sh DEVICE_GROUPS.
check-device:
	python -m pytest tests/test_secp256k1_jax.py \
	  tests/test_keccak_jax.py tests/test_keccak_pallas.py \
	  tests/test_witness_jax.py tests/test_witness_fused.py \
	  tests/test_mpt_jax.py tests/test_parallel.py tests/test_graft_entry.py -q

native:
	python -c "from phant_tpu.utils.native import build_native; print(build_native(verbose=True))"

# Both halves of the dynamic-analysis surface (SURVEY §5 sanitizers
# slot): ASan+UBSan over the native C++ runtime, then phantsan — the
# Eraser-style lockset race detector (phant_tpu/analysis/sanitizer.py) —
# over the Python serving path. check.sh additionally runs the full
# serving group under PHANT_SANITIZE=1 at pipeline depth 2.
sanitize: sanitize-native sanitize-py

sanitize-native:
	mkdir -p build
	g++ -std=c++17 -O1 -g -fsanitize=address,undefined -fno-sanitize-recover=all \
	  -Wall -Werror -Wno-maybe-uninitialized -o build/native_selftest \
	  native/keccak.cc native/packer.cc native/secp256k1.cc native/engine.cc \
	  native/selftest.cc
	./build/native_selftest

# Lockset-sanitized pytest subset: instrumented Lock/RLock proxies +
# per-field lockset tracking on the registered shared classes; ANY
# two-stack race report fails the session (tests/conftest.py
# pytest_sessionfinish). Depth 2 keeps the pipelined pack/dispatch/
# resolve overlap — the schedule phantsan has actually caught races in.
sanitize-py:
	PHANT_SANITIZE=1 PHANT_SCHED_PIPELINE_DEPTH=2 JAX_PLATFORMS=cpu \
	  python -m pytest -q tests/test_sanitizer.py tests/test_serving.py \
	  tests/test_post_root.py tests/test_sender_lane.py

# Scheduler soak smoke (scripts/check.sh runs it after the pytest groups):
# a live Engine API server on the CPU backend takes a few hundred
# concurrent requests — serial-lane newPayloads, batching-lane stateless
# verifications, health/metrics scrapes — and must serialize mutation
# exactly once, coalesce witness batches, shed nothing, and drain clean.
# It then induces ONE executor crash in a throwaway server and asserts the
# obs flight recorder wrote a well-formed postmortem dump (build/flight/)
# that names the crashing batch and its request trace ids, and finishes
# with a <=60s fixed-seed scripts/loadgen.py overload sweep asserting the
# QoS contract: zero serial-lane sheds, nonzero adaptive-wait
# adjustments, no tenant starvation, slow-loris connections reaped.
soak:
	JAX_PLATFORMS=cpu python scripts/soak.py

# Open-loop serving load harness (minutes): Poisson arrivals + bursts + slow-loris
# against a real EngineAPIServer, saturation curve + p50/p99/p999 +
# per-tenant fairness verdicts. See README "Serving: QoS".
loadgen:
	JAX_PLATFORMS=cpu python scripts/loadgen.py --duration 30

# Metric-name drift gate: thin shim over phantlint's METRICNAME rule
# (one checker — see `make lint`): every emitted name must be a literal,
# sanitize to phant_[a-z0-9_]+, and carry a trace.METRIC_HELP entry.
# Keep in sync with README "Observability" / "Static analysis".
metrics-lint:
	JAX_PLATFORMS=cpu python scripts/metrics_lint.py
