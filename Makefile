# CI entry points (reference ships build+test automation,
# /root/reference/.github/workflows/build.yml; this is the TPU-native repo's
# equivalent — `.github/workflows/ci.yml` calls these same targets).
#
# Everything here runs on an 8-virtual-device CPU mesh: the root conftest.py
# flips JAX to the cpu backend before it initializes, so no TPU is needed (or
# claimed — a chip belongs to one process at a time). `make ci` is the one
# command that must stay green. The chip itself is driven by `python
# chip_smoke.py` and `python3 -m benchmark.run` (README "Running it").

PY ?= python
CPU_ENV = env JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: ci test dryrun chip-smoke-cpu native lint lint-fast lint-budget \
	weave capsule-smoke timeline-smoke

ci: lint test dryrun weave capsule-smoke timeline-smoke

# the full static-analysis + invariant-guard suite (tools/oelint): eleven
# passes — trace-hazard (recompile hazards in jit-reachable code), host-sync
# (device_get discipline in `# oelint: hot-path` fns), sharding
# (PartitionSpec placement-flow consistency), spmd-divergence (per-process
# host control flow upstream of collectives), hlo-budget (compiled
# collective counts vs tools/oelint/hlo_budget.json), implicit-reshard
# (GSPMD-inserted collectives with no traced-op attribution), lockset
# (`# guarded-by:` discipline + lock-ordering cycles), atomicity
# (check-then-act split across a lock release), cond-wait (Condition.wait
# predicate loops, notify under the lock), thread-lifecycle (every thread
# has a reachable join), metrics (name hygiene). CPU-only, no chip; passes
# run concurrently and the compiles are cached on a source digest — warm
# runs finish in seconds (<= 25 s budget).
lint:
	$(CPU_ENV) $(PY) -m tools.oelint

# fast local iteration: lint only files changed vs HEAD (skips the
# hlo-budget/implicit-reshard compile unless exchange/trainer/ops paths
# changed)
lint-fast:
	$(CPU_ENV) $(PY) -m tools.oelint --changed-only

# regenerate the pinned HLO collective budget after an INTENTIONAL
# collective change; commit the resulting json diff
lint-budget:
	$(CPU_ENV) $(PY) -m tools.oelint --update-budget

# deterministic concurrency testing (tools/oeweave): explore seeded-random +
# preemption-bounded interleavings of the threaded control plane (subscriber
# state machine, micro-batcher, persister, placement watcher, offload store,
# sketch worker, reporter, SLO evaluator) on a cooperative scheduler; any
# failing schedule prints a replay token that reproduces it bit-for-bit.
# ~60 s budget; typical full run is a few seconds.
weave:
	$(CPU_ENV) $(PY) -m tools.oeweave --budget-s 60

# the full battery (mesh collectives, serving HA processes, persist crash
# consistency, planted-signal AUC regression, keras parity, ...)
test:
	$(PY) -m pytest tests/ -q

# the driver's multi-chip validation: jit + execute full train steps (DP +
# row-sharded tables + all_to_all, packed scan, 63-bit ids, host-cached scan,
# ring-attention CP) over an 8-device mesh
dryrun:
	$(CPU_ENV) $(PY) -c "import __graft_entry__ as g; \
	fn, args = g.entry(); import jax; out = jax.jit(fn)(*args); \
	print('entry OK, loss', float(out['loss'])); g.dryrun_multichip(8)"

# rehearse chip_smoke.py's three stages (train -> serve -> mesh) at a tiny size
# on 4 virtual CPU devices before spending chip time on it; the explicit sizes
# plus JAX_PLATFORMS=cpu are what the script takes as "the caller asked for a
# CPU rehearsal" — without them it refuses to run off-chip
chip-smoke-cpu:
	env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	$(PY) chip_smoke.py --vocabulary 65536 --batch 256 --scan-steps 4

# the flight-data layer end to end: arm capsules in a temp dir, force one
# trigger, and round-trip it through the offline renderer — proves the
# failure path (capsule assembly + atomic write + report) stays importable
# and renderable without a live process
capsule-smoke:
	$(CPU_ENV) $(PY) -c "import tempfile, glob, os; \
	from openembedding_tpu.utils import capsule, metrics, history, trace; \
	d = tempfile.mkdtemp(prefix='capsmoke'); capsule.configure(d); \
	metrics.observe('train.steps', 3.0); \
	history.HISTORY.sample_registry(); \
	trace.event('health', 'nonfinite', source='smoke'); \
	p = capsule.trigger('smoke', origin='make capsule-smoke'); \
	assert p and os.path.exists(p), 'capsule not written'; \
	import tools.capsule_report as cr; \
	text = cr.render(cr.load(p)); \
	assert 'reason=smoke' in text and 'train.steps' in text, text; \
	print('capsule smoke OK:', os.path.basename(p))"

# the fleet-causality surface end to end: two in-process serving nodes,
# Cristian clock probes against both /timelinez endpoints, one merged
# skew-corrected timeline — proves the scrape+merge path stays green without
# a real fleet
timeline-smoke:
	$(CPU_ENV) $(PY) -c "import tempfile, threading; \
	from openembedding_tpu.serving import make_server; \
	from openembedding_tpu.utils import trace; \
	from tools import fleet_timeline as ftl; \
	srvs = [make_server(tempfile.mkdtemp(prefix='tlsmoke')) \
	        for _ in range(2)]; \
	[threading.Thread(target=s.serve_forever, daemon=True).start() \
	 for s in srvs]; \
	urls = ['http://127.0.0.1:%d' % s.server_address[1] for s in srvs]; \
	trace.event('serving', 'smoke', source='make timeline-smoke'); \
	nodes = []; \
	[nodes.append((u, *ftl.probe(u, probes=2))) for u in urls]; \
	items = ftl.merge([(n, d, o) for n, d, o in nodes]); \
	assert items, 'merged fleet timeline is empty'; \
	print(ftl.render(items, limit=5)); \
	[s.shutdown() for s in srvs]; \
	print('timeline smoke OK: %d merged items' % len(items))"

# build the native data-path extension explicitly (the package also builds it
# on demand at import; this target surfaces compiler errors directly)
native:
	$(CPU_ENV) $(PY) -c "from openembedding_tpu import native; \
	native.build(); print('native extension OK')"
