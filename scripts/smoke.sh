#!/usr/bin/env bash
# Smoke check: the diagnostics self-check (round-trips a trace file,
# including a simulated killed writer) plus the tier-1 fast subset of
# the suites covering the instrumented hot paths.  Intended as the
# cheap pre-push / CI gate; the full fast tier is ROADMAP.md's tier-1
# command.
#
#   scripts/smoke.sh            # default fast subset (~2-3 min warm)
#   SMOKE_PYTEST_ARGS='-x -k paint' scripts/smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

echo "== diagnostics self-check =="
python -m nbodykit_tpu.diagnostics --self-check

# the doctor's self-check verdict block (the module form works without
# installing the nbodykit-tpu-doctor console script)
echo "== doctor: self-check =="
python -m nbodykit_tpu.diagnostics --doctor --self-check-only

# bench-record gate: a malformed committed BENCH_r*.json fails here;
# regressions print a WARN verdict but pass
echo "== doctor: bench regression gate =="
python -m nbodykit_tpu.diagnostics --regress .

# shard-safety lint gate: any finding not grandfathered in the
# committed lint_baseline.json fails the smoke run (the module form
# works without installing the nbodykit-tpu-lint console script).
# Since nbkl v2 the surface includes bench.py and the interprocedural
# NBK103/NBK5xx analyses run as part of the same gate.
echo "== shard-safety lint gate =="
python -m nbodykit_tpu.lint --baseline lint_baseline.json \
    nbodykit_tpu/ tests/_multihost_worker.py bench.py

# machine-readable per-family counts: the gate consumes the --stats
# JSON so a new finding in ANY family (incl. NBK103/NBK5xx) fails
# loudly with the per-family split, and regress.py records the same
# shape per round in BENCH_HISTORY.json
echo "== lint stats gate (per-family JSON) =="
python -m nbodykit_tpu.lint --stats --baseline lint_baseline.json \
    nbodykit_tpu/ tests/_multihost_worker.py bench.py | python -c '
import json, sys
stats = json.load(sys.stdin)
assert stats["gate"] == "OK", stats
assert stats["total"]["new"] == 0, stats
fams = stats["families"]
missing = {"NBK1", "NBK2", "NBK3", "NBK4", "NBK5",
           "NBK6", "NBK7", "NBK8"} - set(fams)
assert not missing, "family axis missing: %s" % missing
# NBK6xx/NBK7xx/NBK8xx were triaged in-PR (fixes + audited pragmas),
# so the budget for BOTH columns is zero: nothing new may appear and
# nothing may ever be grandfathered into the baseline for these
# families
for fam in ("NBK6", "NBK7", "NBK8"):
    assert fams[fam]["new"] == 0, (fam, fams[fam])
    assert fams[fam]["baselined"] == 0, (fam, fams[fam])
print("lint stats OK: " + "  ".join(
    "%s=%d+%d" % (k, v["new"], v["baselined"])
    for k, v in sorted(fams.items())))
'

# bounded symbolic-peak report for the north-star 1024^3 config
# (bench.py + the dfft lowmem drivers): proves the documented buffer
# contracts still derive from the source — the donated lowmem driver
# stays inside the v5e budget, the fused pipeline books over it (the
# model errs high; the chip's compiler accepts the fused program)
echo "== memory report: 1024^3 north-star config (bounded) =="
python -m nbodykit_tpu.lint --memory-report --nmesh 1024 \
    --npart 1e8 bench.py nbodykit_tpu/parallel/dfft.py | python -c '
import sys
text = sys.stdin.read()
sys.stdout.write(text)
assert "OVER BUDGET" in text, "fused pipeline should exceed budget"
line = next(l for l in text.splitlines() if "rfftn_single_lowmem" in l)
assert "OVER BUDGET" not in line, (
    "lowmem driver exceeded the budget: " + line)
'

# pencil branch of the memory model (docs/PERF.md): the documented
# 2-buffer eager contract (stage-2 donates stage-1) must keep pricing
# the north-star config — a drift between PENCIL_BUFFERS and the plan
# fails here, not on chip
echo "== memory plan: pencil buffer contract (1024^3, 8 dev) =="
python -c '
from nbodykit_tpu.parallel.dfft import PENCIL_BUFFERS
from nbodykit_tpu.pmesh import memory_plan
plan = memory_plan(1024, int(1e8), ndevices=8, fft_decomp="pencil")
assert plan["fft_pencil_buffers"] == PENCIL_BUFFERS == 2, plan
assert plan["fft_pencil"] == "2x4", plan
assert plan["fft_pencil_pad"] >= 1.0, plan
slab = memory_plan(1024, int(1e8), ndevices=8)
assert plan["fft_workspace"] >= slab["fft_workspace"], (plan, slab)
print("pencil plan OK: %s buffers=%d pad=%.4f fft_ws=%.2f GB" % (
    plan["fft_pencil"], plan["fft_pencil_buffers"],
    plan["fft_pencil_pad"], plan["fft_workspace"] / 2**30))
'

# pencil dist_rfftn end-to-end gate: a 4x2 pencil transform at
# mesh128 must match the slab path and round-trip through c2r at
# double precision — the two group transposes run for real on the
# 8-device CPU mesh
echo "== pencil FFT roundtrip gate (mesh128, 4x2) =="
python -c '
from nbodykit_tpu._jax_compat import set_cpu_devices
set_cpu_devices(8)
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
import jax.numpy as jnp
from nbodykit_tpu.parallel import dfft
from nbodykit_tpu.parallel.runtime import cpu_mesh, pencil_mesh
x = jnp.asarray(np.random.RandomState(7).standard_normal(
    (128, 128, 128)), jnp.float64)
pm = pencil_mesh(4, 2)
y = dfft.dist_rfftn(x, pm)
slab = dfft.dist_rfftn(x, cpu_mesh())
np.testing.assert_allclose(np.asarray(y), np.asarray(slab),
                           atol=1e-10)
back = dfft.dist_irfftn(y, 128, pm)
err = float(jnp.max(jnp.abs(back - x)))
assert err < 1e-10, err
print("pencil roundtrip OK: mesh128 4x2, max|irfftn(rfftn(x))-x| "
      "= %.3e" % err)
'

# halved-bytes precision gate (docs/PERF.md): a mesh64 FFTPower with
# bf16 mesh storage AND bf16 all_to_all payloads on the 8-device CPU
# mesh must stay inside the asserted P(k) budget vs the full-width
# oracle up to k_Nyquist/2, with identical mode counts — the bounded
# form of tests/test_precision.py, run on every smoke
echo "== precision gate (mesh64, bf16 mesh + bf16 a2a) =="
python -c '
from nbodykit_tpu._jax_compat import set_cpu_devices
set_cpu_devices(8)
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
import nbodykit_tpu
from nbodykit_tpu.lab import ArrayCatalog, FFTPower
from nbodykit_tpu.parallel.runtime import cpu_mesh, use_mesh
NMESH, BOX = 64, 200.0
KMIN, DK = 0.31 * (2 * np.pi / BOX), 2.6718 * (2 * np.pi / BOX)
pos = np.random.RandomState(42).uniform(0, BOX, (10000, 3))
def pk(**opts):
    with use_mesh(cpu_mesh()):
        with nbodykit_tpu.set_options(**opts):
            cat = ArrayCatalog({"Position": pos}, BoxSize=BOX)
            r = FFTPower(cat, mode="1d", Nmesh=NMESH, kmin=KMIN, dk=DK)
    return (np.asarray(r.power["k"], "f8"),
            np.asarray(r.power["power"].real, "f8"),
            np.asarray(r.power["modes"], "f8"))
k0, p0, m0 = pk(mesh_dtype="f4", a2a_compress="none")
k, p, m = pk(mesh_dtype="bf16", a2a_compress="bf16")
np.testing.assert_array_equal(m, m0)
sel = (m0 > 0) & np.isfinite(p0) & (k0 <= 0.5 * np.pi * NMESH / BOX)
err = float((np.abs(p[sel] - p0[sel]) / np.abs(p0[sel]).mean()).max())
assert err < 2e-2, "P(k) budget blown: %.3e" % err
print("precision gate OK: bf16 mesh + bf16 a2a, max P(k) rel err "
      "%.3e < 2e-2 (%d bins <= k_Nyq/2)" % (err, int(sel.sum())))
'

# paint candidate gate (docs/PERF.md): every registered paint
# candidate at a bounded CPU shape (mesh128/1e5, 2 reps) must lower,
# run and deposit finite mass — CI catches a candidate that stops
# lowering before a hardware window wastes its budget on it. Bench
# stdout may carry setup noise, so only the last line is parsed.
echo "== paint candidate gate (mesh128/1e5, all candidates) =="
python bench.py --paint-all 128 100000 2 | python -c '
import json, math, sys
recs = json.loads(sys.stdin.read().strip().splitlines()[-1])
assert recs, "no paint candidates registered"
bad = {n: r["error"] for n, r in recs.items() if "error" in r}
assert not bad, "candidates raised: %r" % bad
for name, rec in sorted(recs.items()):
    assert rec["value"] > 0, (name, rec)
    assert math.isfinite(rec["mass_sum"]) and rec["mass_sum"] > 0, \
        (name, rec["mass_sum"])
print("paint gate OK: " + "  ".join(
    "%s=%.3fs" % (n, r["value"])
    for n, r in sorted(recs.items(), key=lambda kv: kv[1]["value"])))
'

# fault-injected resume smoke (docs/RESILIENCE.md): a 2-rep CPU bench
# is SIGKILLed entering rep 2 by the fault harness, then relaunched —
# the relaunch must resume from the checkpoint and flush one complete
# record stamped resumed: true. This rehearses the round-5 evidence
# loss end to end on every smoke run.
echo "== fault-injected kill/resume smoke =="
SMOKE_TMP=$(mktemp -d)
trap 'rm -rf "$SMOKE_TMP"' EXIT
smoke_env=(env JAX_PLATFORMS=cpu BENCH_REPS=2 BENCH_PHASES=0
           BENCH_STAGED_PATH="$SMOKE_TMP/STAGED.json"
           BENCH_DETAIL_PATH="$SMOKE_TMP/DETAIL.json"
           BENCH_CKPT_DIR="$SMOKE_TMP/CKPT"
           BENCH_TRACE_DIR="$SMOKE_TMP/TRACE")
rc=0
"${smoke_env[@]}" NBKIT_FAULTS='bench.rep@2:kill' \
    python bench.py --config 32 2000 || rc=$?
[ "$rc" -eq 137 ] || { echo "expected SIGKILL (137), got rc=$rc"; exit 1; }
"${smoke_env[@]}" python bench.py --config 32 2000 > "$SMOKE_TMP/rec.json"
python - "$SMOKE_TMP" <<'EOF'
import json, os, sys
tmp = sys.argv[1]
rec = json.loads(open(os.path.join(tmp, 'rec.json')).read().strip().splitlines()[-1])
assert rec.get('resumed') is True, rec
assert rec.get('value', -1) > 0 and rec.get('unit') == 's', rec
assert not [f for f in os.listdir(os.path.join(tmp, 'CKPT'))
            if f.endswith('.ckpt.json')], 'checkpoint not consumed'
print('resume smoke OK: %(metric)s resumed -> %(value)s s' % rec)
EOF

# multi-tenant serve gate (docs/SERVING.md): a 24-request synthetic
# trace with a mid-request device loss injected at the 3rd attempt —
# exactly one request retries (batching disabled so the fault lands on
# a single tenant), nothing is lost, every submission gets a structured
# verdict, p99 is recorded
echo "== serve trace gate (24 req, injected fault) =="
env JAX_NUM_CPU_DEVICES=2 \
    NBKIT_FAULTS='serve.request.attempt@3:unavailable' \
    python bench.py --serve-trace 24 1 1 0 > "$SMOKE_TMP/serve.json"
python - "$SMOKE_TMP" <<'EOF'
import json, os, sys
rec = json.loads(open(os.path.join(
    sys.argv[1], 'serve.json')).read().strip().splitlines()[-1])
assert rec['lost'] == 0, rec
assert rec['retried'] == 1, rec
assert rec['p99_s'] > 0, rec
resolved = (rec['completed'] + rec['rejected'] + rec['evicted']
            + rec['failed'])
assert resolved == rec['submitted'], rec
assert rec['faults_injected'], rec
print('serve gate OK: %(completed)d/%(submitted)d completed, '
      'retried=%(retried)d lost=%(lost)d p99=%(p99_s).3fs' % rec)
EOF

# ingestion plane gate (docs/INGEST.md): a small on-disk catalog is
# served twice via data_ref on a 2-device sub-mesh — both requests
# complete with bit-equal spectra and the second rides the worker's
# content-addressed CatalogCache (ingestion paid once, nothing lost)
echo "== ingest data_ref gate (2 requests, 1 cache hit) =="
python - "$SMOKE_TMP" <<'EOF'
import os, sys
import numpy as np
from nbodykit_tpu._jax_compat import set_cpu_devices
set_cpu_devices(2)
import jax
jax.config.update('jax_enable_x64', True)
import nbodykit_tpu
from nbodykit_tpu.serve import COMPLETED, AnalysisRequest, AnalysisServer
path = os.path.join(sys.argv[1], 'smoke_catalog.bin')
np.random.RandomState(11).uniform(
    0.0, 100.0, (2048, 3)).astype('f4').tofile(path)
ref = {'path': path, 'format': 'binary',
       'columns': {'Position': 'Position'},
       'options': {'dtype': [('Position', ('f4', 3))]}}
with nbodykit_tpu.set_options(ingest_chunk_rows=1024), \
        AnalysisServer(per_task=2, max_queue=4) as srv:
    r1 = srv.wait(srv.submit(AnalysisRequest(
        nmesh=32, data_ref=ref, deadline_s=600.0)))
    r2 = srv.wait(srv.submit(AnalysisRequest(
        nmesh=32, data_ref=ref, deadline_s=600.0)))
    summary = srv.summary()
assert r1.status == COMPLETED and r2.status == COMPLETED, (r1, r2)
np.testing.assert_array_equal(np.asarray(r1.y), np.asarray(r2.y))
assert summary['ingest_requests'] == 2, summary
assert summary['ingest_cache_hits'] == 1, summary
assert summary['lost'] == 0, summary
print('ingest gate OK: 2 data_ref requests completed, 1 cache hit, '
      'bit-equal P(k), lost=0')
EOF

# data-integrity gate (docs/INTEGRITY.md): a mesh64 FFT bench under
# integrity='cheap' with one stuck-at-one corruption injected into an
# all_to_all payload — the wire checksum must catch it, the supervisor
# retries once against the strike ledger, and the record is stamped
# integrity: {violations: 1, retried: 1}
echo "== integrity gate (mesh64, injected a2a corruption) =="
env JAX_NUM_CPU_DEVICES=8 NBKIT_FAULTS='a2a.payload@1:corrupt' \
    python bench.py --integrity 64 100000 2 > "$SMOKE_TMP/integ.json"
python - "$SMOKE_TMP" <<'EOF'
import json, os, sys
rec = json.loads(open(os.path.join(
    sys.argv[1], 'integ.json')).read().strip().splitlines()[-1])
assert rec.get('integrity') == {'violations': 1, 'retried': 1}, rec
assert rec.get('value', -1) > 0 and rec.get('unit') == 's', rec
print('integrity gate OK: 1 injected corruption caught at %s, '
      'retried clean, overhead %.1f%%' % (
          ','.join(rec.get('violation_sites', ['?'])),
          100.0 * rec.get('overhead', 0.0)))
EOF

# shadow-verification gate (docs/INTEGRITY.md): a seeded request with
# verify=True is re-executed on the OTHER sub-mesh after completing —
# the uncompressed program must come back bit-identical, proving two
# disjoint device groups agree on the full pipeline
echo "== shadow verification gate (verify=True, 2 sub-meshes) =="
python - <<'EOF'
from nbodykit_tpu._jax_compat import set_cpu_devices
set_cpu_devices(8)
import jax
jax.config.update('jax_enable_x64', True)
from nbodykit_tpu.serve import COMPLETED, AnalysisRequest, AnalysisServer
with AnalysisServer(per_task=4) as srv:
    assert len(srv.meshes) >= 2, srv.meshes
    r = srv.wait(srv.submit(AnalysisRequest(
        nmesh=32, npart=2000, seed=3, verify=True, deadline_s=600.0)))
    summary = srv.summary()
assert r.status == COMPLETED, r
assert summary['shadow_verified'] == 1, summary
assert summary['shadow_mismatch'] == 0, summary
print('shadow gate OK: 1 request shadow-verified bit-identical '
      'across sub-meshes, 0 mismatches')
EOF

# forward-model gate (docs/FORWARD.md): the differentiable pipeline
# must stay differentiable on every smoke run — a bounded 64^3 mesh /
# 1e4-particle KDK step is checked against a central finite difference
# (eps below the CIC kink noise at f8), then one Forward request rides
# the serve plane end to end: admitted with the reverse-pass memory
# branch, completed, nothing lost
echo "== forward gate (64^3/1e4 grad check + 1-request serve) =="
python - <<'EOF'
import jax
jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_enable_x64', True)
import jax.numpy as jnp
from nbodykit_tpu.forward import ForwardModel, make_loss
model = ForwardModel(64, 22 ** 3, BoxSize=1000.0, pm_steps=1,
                     dtype='f8')
truth = model.linear_modes(0)
obs = jax.jit(model.density)(truth)
loss = make_loss(model, obs, noise_std=0.1)
w0 = model.lattice.c2r(model.lattice.generate_whitenoise(1)) * 0.05
g = jax.jit(jax.grad(loss))(w0)
d = model.lattice.c2r(model.lattice.generate_whitenoise(2))
d = d / jnp.sqrt(jnp.sum(d * d))
eps = 1e-6
lj = jax.jit(loss)
fd = (float(lj(w0 + eps * d)) - float(lj(w0 - eps * d))) / (2 * eps)
dot = float(jnp.sum(g * d))
rel = abs(fd - dot) / max(abs(fd), 1e-300)
assert rel < 1e-4, "grad check VIOLATED: fd=%r grad=%r rel=%.3e" % (
    fd, dot, rel)
print('forward grad OK: mesh64/n%d kdk, |fd-grad|/|fd| = %.3e'
      % (model.npart, rel))
EOF
python - <<'EOF'
from nbodykit_tpu._jax_compat import set_cpu_devices
set_cpu_devices(8)
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np
from nbodykit_tpu.serve import COMPLETED, AnalysisRequest, AnalysisServer
with AnalysisServer(per_task=4) as srv:
    r = srv.wait(srv.submit(AnalysisRequest(
        algorithm='Forward', nmesh=16, npart=8 ** 3, pm_steps=1,
        seed=5, deadline_s=600.0)))
    summary = srv.summary()
assert r.status == COMPLETED, r
y = np.asarray(r.y)
assert np.isfinite(y).all() and np.abs(y).sum() > 0, y
assert summary['lost'] == 0, summary
print('forward serve OK: 1 Forward request completed '
      '(mesh16/n512 x1 step), lost=0')
EOF

# bispectrum gate (docs/BISPECTRUM.md): the Scoccimarro FFT estimator
# at mesh 16 must match a brute-force numpy oracle on the equilateral
# diagonal — every closed (mod-16) within-shell mode triangle summed
# directly from the full c2c spectrum — with bit-exact triangle
# counts; then one Bispectrum request rides the serve plane end to
# end: admitted under the 3-shell-field pricing branch, completed
# with finite shells, nothing lost
echo "== bispectrum gate (mesh16 equilateral oracle + serve) =="
python - <<'EOF'
from nbodykit_tpu._jax_compat import set_cpu_devices
set_cpu_devices(8)
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np
import jax.numpy as jnp
from nbodykit_tpu.algorithms.bispectrum import fft_bispectrum
from nbodykit_tpu.pmesh import ParticleMesh
N, L, nbins = 16, 100.0, 3
pm = ParticleMesh(Nmesh=N, BoxSize=L, dtype='f8')
real = np.random.RandomState(5).standard_normal((N, N, N))
B, ntri = fft_bispectrum(pm, pm.r2c(jnp.asarray(real)), nbins)
dk = np.fft.fftn(real) / N ** 3
fx = np.fft.fftfreq(N, 1.0 / N).astype(int)
qx, qy, qz = np.meshgrid(fx, fx, fx, indexing='ij')
q = np.stack([qx, qy, qz], -1).reshape(-1, 3)
isq = (q ** 2).sum(1)
dflat = dk.reshape(-1)
for b in range(nbins):
    lo2, hi2 = (b + 1) ** 2, (b + 2) ** 2
    qs = q[(isq >= lo2) & (isq < hi2)]
    ds = dflat[(isq >= lo2) & (isq < hi2)]
    q3 = (-(qs[:, None, :] + qs[None, :, :])) % N
    s3 = (((q3 + N // 2) % N - N // 2) ** 2).sum(-1)
    idx = (q3[..., 0] * N + q3[..., 1]) * N + q3[..., 2]
    m = (s3 >= lo2) & (s3 < hi2)
    S = (ds[:, None] * ds[None, :] * dflat[idx])[m].sum()
    cnt = int(m.sum())
    assert int(ntri[b, b, b]) == cnt, (b, ntri[b, b, b], cnt)
    want = L ** 6 * S.real / cnt
    rel = abs(float(B[b, b, b]) - want) / max(abs(want), 1e-300)
    assert rel < 1e-6, (b, float(B[b, b, b]), want, rel)
print('bispectrum oracle OK: mesh16 equilateral, %d shells '
      'bit-exact ntri, B rel err < 1e-6' % nbins)
EOF
python - <<'EOF'
from nbodykit_tpu._jax_compat import set_cpu_devices
set_cpu_devices(8)
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np
from nbodykit_tpu.parallel.runtime import cpu_mesh, use_mesh
from nbodykit_tpu.serve import COMPLETED, AnalysisRequest, AnalysisServer
with use_mesh(cpu_mesh(1)):
    srv = AnalysisServer(per_task=1)
with srv:
    r = srv.wait(srv.submit(AnalysisRequest(
        algorithm='Bispectrum', nmesh=16, npart=4000, nbins=3,
        seed=9, deadline_s=600.0)), timeout=600)
    summary = srv.summary()
assert r.status == COMPLETED, r
y = np.asarray(r.y)
assert np.isfinite(y).all() and y.shape == (3,), y
assert np.asarray(r.nmodes).min() > 0, r.nmodes
assert summary['lost'] == 0, summary
print('bispectrum serve OK: 1 Bispectrum request completed '
      '(mesh16, 3 shells, finite B), lost=0')
EOF

# region gate (docs/SERVING.md "Region"): a two-fleet router trace
# with a third fleet joining mid-trace — the bench asserts the whole
# region posture in one shot: >=1 content-addressed result-cache hit
# (repeat slices of the trace), >=1 structured spill redirect (the
# closed-loop slam overflows spill_depth), the elastic join sealed
# with reformed_from/to stamps, fair share holding under the bulk
# tenant's priority-2 flood (throttled > 0, starved == 0), cached
# bytes bit-identical to a fresh recomputation, and zero lost
echo "== region gate (40 req, 2 fleets + mid-trace join) =="
env JAX_NUM_CPU_DEVICES=2 \
    python bench.py --region-trace 40 2 1 0 > "$SMOKE_TMP/region.json"
python - "$SMOKE_TMP" <<'EOF'
import json, os, sys
rec = json.loads(open(os.path.join(
    sys.argv[1], 'region.json')).read().strip().splitlines()[-1])
assert rec['lost'] == 0, rec
assert rec['result_hits'] >= 1, rec
assert rec['spills'] >= 1, rec
assert rec['joins'] == 1, rec
assert rec['reformed_from'] == 2 and rec['reformed_to'] == 3, rec
assert rec['throttled'] > 0, rec
assert rec['starved'] == 0, rec
assert rec['unverified_as_verified'] == 0, rec
assert rec['cache_bit_identical'] is True, rec
assert 'error' not in rec, rec
print('region gate OK: %(completed)d/%(submitted)d completed over '
      '%(fleet_count)d fleets, hits=%(result_hits)d '
      'spills=%(spills)d joins=%(joins)d throttled=%(throttled)d '
      'starved=%(starved)d lost=%(lost)d' % rec)
EOF

# the rule-tree-produced PartitionSpecs cross shard_map boundaries in
# the paint path; the sharding-flow analyses must stay clean over the
# whole surface with nothing new and nothing grandfathered (the
# NBK6 zero-budget policy from the stats gate, enforced standalone so
# an ingest-plane spec bug fails even if run outside full smoke)
echo "== ingest sharding-flow gate (NBK6xx clean) =="
python -m nbodykit_tpu.lint --select NBK6 nbodykit_tpu/ bench.py
python -m nbodykit_tpu.lint --shard-report nbodykit_tpu/ingest/ \
    nbodykit_tpu/pmesh.py

# the threaded control plane (serve workers, region pacer, exporter
# httpd, fleet monitor, trace heartbeat) must stay free of lock-order
# inversions, cross-thread races and blocking-under-lock — the NBK8
# zero-budget policy from the stats gate, enforced standalone over
# the full tree; the lock report doubles as the human-readable map
# of every lock identity and its acquiring threads
echo "== host-concurrency gate (NBK8xx clean) =="
python -m nbodykit_tpu.lint --select NBK8 nbodykit_tpu/ bench.py
python -m nbodykit_tpu.lint --lock-report nbodykit_tpu/

# fleet survivability gate (docs/RESILIENCE.md): a 2-process gloo
# fleet has rank 1 SIGKILLed entering rep 2 — rank 0's live monitor
# must detect the dead peer and exit DEAD_RANK_EXIT (76) instead of
# wedging in the collective, leaving a sealed 2-rank manifest; the
# 1-process relaunch re-forms the mesh, repartitions the surviving
# shards and resumes from the seal (reformed_from: 2)
echo "== fleet kill/detect/re-form/resume gate (2 proc -> 1) =="
fleet_env=(env JAX_PLATFORMS=cpu
           NBKIT_DIAGNOSTICS="$SMOKE_TMP/FLEET_TRACE"
           NBKIT_DIAGNOSTICS_HEARTBEAT=0.25
           NBKIT_FLEET_DIR="$SMOKE_TMP/FLEET_CKPT"
           NBKIT_FLEET_RECORD="$SMOKE_TMP/fleet_rec.json"
           NBKIT_FLEET_GAP_S=1.5)
mkdir -p "$SMOKE_TMP/FLEET_CKPT"
rc0=0; rc1=0
"${fleet_env[@]}" NBKIT_FAULTS='rank1@bench.rep@2:sigkill' \
    python tests/_multihost_worker.py 127.0.0.1:12377 2 0 fleet \
    > "$SMOKE_TMP/fleet0.log" 2>&1 &
pid0=$!
"${fleet_env[@]}" NBKIT_FAULTS='rank1@bench.rep@2:sigkill' \
    python tests/_multihost_worker.py 127.0.0.1:12377 2 1 fleet \
    > "$SMOKE_TMP/fleet1.log" 2>&1 &
pid1=$!
wait "$pid0" || rc0=$?
wait "$pid1" || rc1=$?
[ "$rc0" -eq 76 ] || { echo "rank 0: expected DEAD_RANK_EXIT (76)," \
    "got rc=$rc0"; tail -40 "$SMOKE_TMP/fleet0.log"; exit 1; }
[ "$rc1" -eq 137 ] || { echo "rank 1: expected SIGKILL (137), got" \
    "rc=$rc1"; tail -40 "$SMOKE_TMP/fleet1.log"; exit 1; }
"${fleet_env[@]}" python tests/_multihost_worker.py none 1 0 fleet \
    > "$SMOKE_TMP/fleet_resume.log" 2>&1 \
    || { tail -40 "$SMOKE_TMP/fleet_resume.log"; exit 1; }
python - "$SMOKE_TMP" <<'EOF'
import json, os, sys
tmp = sys.argv[1]
rec = json.load(open(os.path.join(tmp, 'fleet_rec.json')))
assert rec.get('resumed') is True, rec
assert rec.get('reformed_from') == 2 and rec.get('reformed_to') == 1, rec
assert rec.get('completed') == rec.get('reps'), rec
from nbodykit_tpu.diagnostics import read_trace
records, _ = read_trace(os.path.join(tmp, 'FLEET_TRACE'))
dead = [r for r in records if r.get('t') == 'span'
        and r.get('name') == 'resilience.fleet.dead_rank']
assert dead, 'no dead-rank event in the monitor trace'
print('fleet gate OK: dead rank detected, mesh re-formed '
      '%(reformed_from)d -> %(reformed_to)d, resumed at rep '
      '%(resumed_reps)d' % rec)
EOF

# observability gate (docs/OBSERVABILITY.md): a 24-request region
# trace with the live export plane enabled — every request must
# render a fully linked orphan-free waterfall, the telemetry
# endpoint must scrape (Prometheus text with real per-fleet labels,
# SLO snapshot), and an injected preemption must seal the flight
# recorder next to the trace
echo "== observability gate (24-req region trace + export + flight) =="
env NBKIT_DIAGNOSTICS_SYNC=0 NBKIT_TRACE_EXEMPLAR=0.02 \
    JAX_NUM_CPU_DEVICES=2 python - "$SMOKE_TMP" <<'EOF'
import json, os, sys, urllib.request
import nbodykit_tpu
from nbodykit_tpu.parallel.runtime import cpu_mesh, use_mesh
from nbodykit_tpu.serve import (AnalysisRequest, AnalysisServer,
                                QoSPolicy, Region, ResultCache,
                                ServiceClass)
from nbodykit_tpu.diagnostics import request_report
from nbodykit_tpu.diagnostics.analyze import load_processes
from nbodykit_tpu.diagnostics.export import ensure_exporter, \
    stop_exporter

tmp = sys.argv[1]
tracedir = os.path.join(tmp, 'obs_trace')
os.makedirs(tracedir, exist_ok=True)


def req(i, seed, deadline=300.0):
    return AnalysisRequest(algorithm='FFTPower', nmesh=16, npart=1000,
                           seed=seed, deadline_s=deadline,
                           request_id='obs-%03d' % i)


def fleet():
    with use_mesh(cpu_mesh(1)):
        return AnalysisServer(per_task=1)


qos = QoSPolicy(
    classes=[ServiceClass('interactive'),
             ServiceClass('bulk', rate=4.0, burst=1)],
    tenants={'bulk-sweep': 'bulk'}, default_class='interactive')
with nbodykit_tpu.set_options(diagnostics=tracedir,
                              telemetry_port=0):
    region = Region([('a', fleet()), ('b', fleet())],
                    result_cache=ResultCache(
                        os.path.join(tmp, 'obs_rcache')), qos=qos)
    exp = ensure_exporter()
    assert exp is not None, 'telemetry_port=0 started no exporter'
    tickets = []
    # 16 interactive (4 distinct shapes -> warm cache), 4 repeats
    # (result-cache hits / singleflight), 4 bulk (pacer-held)
    for i in range(16):
        tickets.append(region.submit(req(i, seed=100 + i % 4)))
    for i in range(16, 20):
        tickets.append(region.submit(req(i, seed=100 + i % 4)))
    for i in range(20, 24):
        tickets.append(region.submit(req(i, seed=200 + i),
                                     tenant='bulk-sweep'))
    results = [region.wait(t, timeout=300) for t in tickets]
    assert all(r is not None and r.status == 'completed'
               for r in results), \
        [getattr(r, 'status', None) for r in results]

    # scrape the export plane while the region is live
    text = urllib.request.urlopen(exp.url + '/metrics').read().decode()
    assert 'region_completed_total' in text, text[:400]
    assert 'region_fleet_load{fleet=' in text, text[:400]
    slo = json.loads(urllib.request.urlopen(exp.url + '/slo').read())
    assert 'region' in slo and slo['region']['verdict'] == 'OK', slo
    assert urllib.request.urlopen(exp.url + '/healthz').read() \
        == b'ok\n'

    summary = region.summary()
    region.shutdown()
    # injected preemption: the SIGTERM drain path must seal the
    # flight ring beside the trace
    region.router.fleets()[0].server.preempt(grace_s=2.0)
stop_exporter()

procs, torn = load_processes(tracedir)
assert torn == 0, torn
rep = request_report(procs)
assert rep['traces'] >= 24, rep['traces']
assert rep['complete'] == rep['traces'], rep['incomplete']
assert rep['orphan_spans'] == 0, rep['orphan_spans']
assert 'qos_hold' in rep['stage_totals_s'], rep['stage_totals_s']

dumps = [f for f in os.listdir(tracedir) if f.startswith('flight-')]
assert dumps, 'preemption sealed no flight dump'
body = json.load(open(os.path.join(tracedir, dumps[0])))
assert body['reason'].startswith('serve.preempt'), body['reason']
assert body['requests'], 'flight ring empty'
print('observability gate OK: %d/%d waterfalls complete, 0 orphans, '
      'slo %s, flight dump %s (%d entries)'
      % (rep['complete'], rep['traces'],
         summary['slo']['verdict'], dumps[0], len(body['requests'])))
EOF

echo "== tier-1 fast subset =="
python -m pytest \
    tests/test_diagnostics.py \
    tests/test_diagnostics_analyze.py \
    tests/test_resilience.py \
    tests/test_fleet.py \
    tests/test_options.py \
    tests/test_serve.py \
    tests/test_region.py \
    tests/test_observability.py \
    tests/test_lint.py \
    tests/test_lint_concurrency.py \
    tests/test_lint_dataflow.py \
    tests/test_lint_shardflow.py \
    tests/test_lint_dtypeflow.py \
    tests/test_jax_compat.py \
    tests/test_pmesh.py \
    tests/test_pencil_fft.py \
    tests/test_paint_kernels.py \
    tests/test_fftpower.py \
    tests/test_forward.py \
    tests/test_bispectrum.py \
    tests/test_counted_exchange.py \
    tests/test_radix.py \
    tests/test_ingest.py \
    -q -m 'not slow' -p no:cacheprovider ${SMOKE_PYTEST_ARGS:-}

echo "smoke OK"
