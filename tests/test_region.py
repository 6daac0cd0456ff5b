"""Tests for nbodykit_tpu.serve.region: the multi-fleet front door.

The load-bearing property test is result-key purity — the cache
address is EXACTLY ``(program_key, seed|catalog-digest, sorted(jit
options))``: every runtime-only field (priority, deadline_s, verify,
request_id, tenant) perturbs nothing, every jit-reaching option
perturbs the address.  Around it: torn-entry corruption (detected,
recomputed, never served), LRU eviction, router verdict grammar
(affinity / spill / rerouted_dead / catalog_home / no_fleet), the
singleflight follower path, QoS bucket determinism + the fair-share
and starvation ledgers + chaos at the admission gate, the
verified-stamp contract under the ``region.result.stamp`` corrupt
rule, elastic grow with ``reformed_from/to`` manifest stamps, the
``data_steal_grace_s`` satellite, region-trace synthesis, and the
regress posture plumbing."""

import json
import os
import time

import numpy as np
import pytest

import nbodykit_tpu
from nbodykit_tpu import _global_options, diagnostics
from nbodykit_tpu.diagnostics import REGISTRY
from nbodykit_tpu.parallel.runtime import cpu_mesh, use_mesh
from nbodykit_tpu.resilience import reset_faults
from nbodykit_tpu.resilience.fleet import (FleetCheckpointStore,
                                           reassemble)
from nbodykit_tpu.serve import (COMPLETED, EVICTED, AnalysisRequest,
                                AnalysisServer, QoSPolicy, Region,
                                RegionRouter, RequestResult,
                                ResultCache, ServiceClass,
                                generate_region_trace, result_key)
from nbodykit_tpu.serve.region import (JIT_OPTIONS, Fleet,
                                       catalog_identity, grow,
                                       seal_join)
from nbodykit_tpu.serve.region.qos import _Bucket
from nbodykit_tpu.serve.scheduler import affinity


@pytest.fixture(autouse=True)
def _clean_state():
    saved = _global_options.copy()
    REGISTRY.reset()
    reset_faults()
    yield
    REGISTRY.reset()
    reset_faults()
    diagnostics.configure(None)
    _global_options.clear()
    _global_options.update(saved)


# ---------------------------------------------------------------------------
# fakes: just enough AnalysisServer surface for region mechanics

class _FakeTicket(object):
    def __init__(self, request, verify=False):
        self.request = request
        self.verify = verify


class _FakeServer(object):
    """Deterministic stand-in: completes (or evicts) instantly, with
    seed-dependent spectra so cached bytes are checkable."""

    def __init__(self, ndevices=1, status=COMPLETED, verify=False,
                 accepting=True, queued=0):
        self.ndevices = ndevices
        self.meshes = [None]
        self.status = status
        self.verify = verify
        self.accepting = accepting
        self.queued = queued
        self.submitted = []

    def load(self):
        return {'queued': self.queued, 'inflight': 0,
                'accepting': self.accepting, 'workers': 1}

    def submit(self, request):
        self.submitted.append(request)
        return _FakeTicket(request, verify=self.verify)

    def wait(self, ticket, timeout=None):
        req = ticket.request
        if self.status == COMPLETED:
            return RequestResult(
                req.request_id, COMPLETED, x=np.arange(4.0) + 0.5,
                y=np.arange(4.0) * (req.seed + 1),
                nmodes=np.ones(4, dtype=np.int64), latency_s=1e-4,
                algorithm=req.algorithm,
                shape_class=req.shape_class)
        return RequestResult(
            req.request_id, self.status, reason={'code': 'deadline'},
            latency_s=1e-4, algorithm=req.algorithm,
            shape_class=req.shape_class)

    def summary(self):
        return {'submitted': len(self.submitted), 'lost': 0}

    def shutdown(self, drain=True, timeout=None):
        self.accepting = False


# ---------------------------------------------------------------------------
# result-key purity (the satellite property test)

def test_result_key_runtime_fields_perturb_nothing():
    base = AnalysisRequest(nmesh=64, npart=100000, seed=5,
                           request_id='a')
    d0, text = result_key(base)
    # every runtime-only knob, together and separately
    twin = AnalysisRequest(nmesh=64, npart=100000, seed=5,
                           priority=2, deadline_s=0.125, verify=True,
                           request_id='completely-different')
    assert result_key(twin)[0] == d0
    # runtime-only OPTIONS perturb nothing either
    with nbodykit_tpu.set_options(
            diagnostics=None, io_verify_checksums=False,
            ingest_overlap=False, data_steal_grace_s=9.5,
            faults='region.qos.admit@99:internal'):
        assert result_key(base)[0] == d0
    # the canonical text carries no runtime field by name
    for forbidden in ('priority', 'deadline', 'verify', 'tenant',
                      'request_id'):
        assert forbidden not in text


def test_result_key_every_jit_option_perturbs():
    base = AnalysisRequest(nmesh=64, npart=100000, seed=5)
    d0, _ = result_key(base)
    perturb = {
        'mesh_dtype': 'bf16', 'a2a_compress': 'bf16',
        'resampler': 'tsc', 'paint_method': 'sort',
        'paint_chunk_size': 12345,
        'paint_streams': 7, 'fft_chunk_bytes': 999,
        'fft_decomp': 'pencil', 'fft_pencil': (2, 4),
        'integrity': 'cheap',
        'ingest_chunk_rows': 4242,
    }
    assert sorted(perturb) == sorted(JIT_OPTIONS)
    digests = {d0}
    for key, value in perturb.items():
        with nbodykit_tpu.set_options(**{key: value}):
            d, _ = result_key(base)
        assert d != d0, 'jit option %r did not perturb' % key
        digests.add(d)
    # all distinct: no two options collide onto one address
    assert len(digests) == len(perturb) + 1
    # program identity and realization input perturb too
    assert result_key(base, ndevices=8)[0] != d0
    assert result_key(AnalysisRequest(nmesh=64, npart=100000,
                                      seed=6))[0] != d0
    assert result_key(AnalysisRequest(nmesh=32, npart=100000,
                                      seed=5))[0] != d0
    # request-scoped option overrides (the admission ladder) key too
    dov, _ = result_key(base, options={'mesh_dtype': 'bf16'})
    assert dov != d0
    # ... but a runtime-only override does not
    assert result_key(base, options={'diagnostics': '/tmp/x'})[0] \
        == d0


def test_catalog_identity_and_data_ref_keys(tmp_path):
    path = str(tmp_path / 'cat.bin')
    np.arange(12, dtype='f4').tofile(path)
    ref = {'path': path, 'format': 'binary',
           'columns': {'Position': 'Position'},
           'options': {'dtype': [('Position', ('f4', 3))]}}
    d0 = catalog_identity(ref)
    assert d0 == catalog_identity(dict(ref))
    # a data_ref request's seed is ignored, exactly as execution
    # ignores it
    r1 = AnalysisRequest(nmesh=32, data_ref=ref, seed=1)
    r2 = AnalysisRequest(nmesh=32, data_ref=ref, seed=999)
    assert result_key(r1)[0] == result_key(r2)[0]
    # rewriting the file mints a new address (size change)
    np.arange(24, dtype='f4').tofile(path)
    assert catalog_identity(ref) != d0
    # a different column map is a different catalog
    other = dict(ref, columns={'Position': 'pos'})
    assert catalog_identity(other) != catalog_identity(ref)


# ---------------------------------------------------------------------------
# the result cache on disk

def test_result_cache_roundtrip_bit_identity(tmp_path):
    cache = ResultCache(str(tmp_path))
    x = np.linspace(0.0, 1.0, 7)
    y = np.array([1e-300, -0.0, 3.141592653589793, 2.0 ** -1049,
                  1e308, -7.25, 0.1])
    nmodes = np.array([1, 2, 3, 4, 5, 6, 7], dtype=np.int64)
    assert cache.get('deadbeef') is None          # cold miss
    cache.put('deadbeef', 'key-text', x, y, nmodes, verified=True)
    got = cache.get('deadbeef')
    # bit-identical round trip, including the denormal and the -0.0
    assert got['x'].dtype == x.dtype
    assert np.array_equal(got['x'], x)
    assert np.array_equal(got['y'], y)
    assert np.array_equal(got['nmodes'], nmodes)
    assert got['y'].tobytes() == y.tobytes()
    assert got['verified'] is True and got['key'] == 'key-text'
    st = cache.stats()
    assert st['hits'] == 1 and st['misses'] == 1 \
        and st['commits'] == 1 and st['corrupt'] == 0
    # a second cache over the same root adopts the committed entries
    again = ResultCache(str(tmp_path))
    assert len(again) == 1 and again.get('deadbeef') is not None


def test_result_cache_torn_entry_never_served(tmp_path):
    cache = ResultCache(str(tmp_path))
    a = np.arange(4.0)
    cache.put('d1', 'k1', a, a, a, verified=True)
    path = cache._path('d1')
    # torn write: truncate mid-file
    data = open(path, 'rb').read()
    with open(path, 'wb') as f:
        f.write(data[:len(data) // 2])
    assert cache.get('d1') is None
    assert not os.path.exists(path), 'torn entry must be unlinked'
    assert cache.stats()['corrupt'] == 1
    # tampered write: valid JSON, flipped verified stamp, stale hash
    cache.put('d2', 'k2', a, a, a, verified=False)
    path = cache._path('d2')
    stored = json.load(open(path))
    stored['body']['verified'] = True
    with open(path, 'w') as f:
        json.dump(stored, f)
    assert cache.get('d2') is None, 'forged stamp must not be served'
    assert cache.stats()['corrupt'] == 2
    assert not os.path.exists(path)
    # recompute-and-recommit heals
    cache.put('d2', 'k2', a, a, a, verified=False)
    assert cache.get('d2')['verified'] is False


def test_result_cache_lru_under_byte_cap(tmp_path):
    cache = ResultCache(str(tmp_path), budget_bytes=1)
    a = np.arange(8.0)
    cache.put('old', 'k', a, a, a)
    cache.put('new', 'k', a, a, a)
    assert cache.get('old') is None, 'LRU entry must be evicted'
    assert cache.stats()['evictions'] == 1
    assert not os.path.exists(cache._path('old'))


# ---------------------------------------------------------------------------
# QoS: deterministic buckets, fair share, chaos at the gate

def test_qos_bucket_due_time_ladder():
    b = _Bucket(rate=1.0, burst=2.0)
    # two burst slots, then the Nth over-burst request waits N/rate
    assert [b.reserve(100.0) for _ in range(5)] \
        == [0.0, 0.0, 1.0, 2.0, 3.0]
    # refill: 2.5 s later two tokens are back (capped at burst)
    b2 = _Bucket(rate=2.0, burst=2.0)
    for _ in range(4):
        b2.reserve(50.0)
    assert b2.reserve(53.0) == pytest.approx(0.0)


def test_qos_policy_validation_and_mapping():
    with pytest.raises(ValueError):
        ServiceClass('bad', rate=0.0)
    with pytest.raises(ValueError):
        QoSPolicy(tenants={'t': 'nope'})
    with pytest.raises(ValueError):
        QoSPolicy(default_class='nope')
    qos = QoSPolicy(tenants={'sweep': 'bulk'})
    assert qos.service_class('sweep').name == 'bulk'
    # unmapped tenants fall to interactive and are never throttled
    assert qos.service_class('stranger').rate is None
    name, delay = qos.reserve('stranger', 0.0)
    assert (name, delay) == ('interactive', 0.0)


def test_qos_gate_chaos_is_structured_rejection():
    server = _FakeServer()
    with nbodykit_tpu.set_options(
            faults='region.qos.admit@1:internal'):
        region = Region([('a', server)], qos=QoSPolicy())
        t = region.submit(AnalysisRequest(nmesh=32, npart=1000),
                          tenant='x')
        res = region.wait(t, timeout=5)
        summary = region.summary()
        region.shutdown()
    assert res.status == 'rejected'
    assert res.reason['code'] == 'qos_unavailable'
    assert summary['lost'] == 0
    assert not server.submitted, 'broken gate must not leak through'


def test_region_fair_share_flood_holds():
    """A bulk tenant floods at self-declared priority 2; per-tenant
    fair share throttles THAT tenant (held to due-times, all still
    completing) and nobody starves, nothing is lost."""
    server = _FakeServer()
    qos = QoSPolicy(
        classes=[ServiceClass('interactive'),
                 ServiceClass('bulk', rate=400.0, burst=2)],
        tenants={'flood': 'bulk'})
    region = Region([('a', server)], qos=qos)
    tickets = [region.submit(
        AnalysisRequest(nmesh=32, npart=1000, seed=i, priority=2,
                        deadline_s=30.0), tenant='flood')
        for i in range(8)]
    tickets += [region.submit(
        AnalysisRequest(nmesh=32, npart=1000, seed=100 + i,
                        deadline_s=30.0), tenant='alice')
        for i in range(3)]
    assert region.drain(timeout=30)
    summary = region.summary()
    region.shutdown()
    assert summary['lost'] == 0
    assert summary['completed'] == 11
    assert summary['qos']['throttled'] == 6      # 8 bulk - burst 2
    assert summary['qos']['starved'] == 0
    assert summary['by_class']['interactive']['completed'] == 3
    assert summary['by_class']['bulk']['completed'] == 8
    for t in tickets:
        assert region.wait(t).ok


def test_qos_throttle_past_deadline_is_structured_eviction():
    server = _FakeServer()
    qos = QoSPolicy(
        classes=[ServiceClass('interactive'),
                 ServiceClass('bulk', rate=0.5, burst=1)],
        tenants={'flood': 'bulk'})
    region = Region([('a', server)], qos=qos)
    first = region.submit(AnalysisRequest(nmesh=32, npart=1000,
                                          deadline_s=1.0),
                          tenant='flood')
    second = region.submit(AnalysisRequest(nmesh=32, npart=1000,
                                           deadline_s=1.0),
                           tenant='flood')
    r1, r2 = region.wait(first, timeout=10), region.wait(second,
                                                         timeout=10)
    summary = region.summary()
    region.shutdown()
    assert r1.ok
    assert r2.status == EVICTED
    assert r2.reason['code'] == 'qos_throttled'
    assert r2.reason['would_wait_s'] == pytest.approx(2.0)
    # a fair-share eviction of a THROTTLED class is not starvation
    assert summary['qos']['starved'] == 0
    assert summary['lost'] == 0


def test_starvation_ledger_counts_unthrottled_deadline_deaths():
    """The failure mode QoS exists to prevent: an interactive
    (unthrottled / policy-free) request dying of old age counts as
    starved — the doctor's WARN number."""
    server = _FakeServer(status=EVICTED)
    region = Region([('a', server)])     # no QoS: the naive region
    t = region.submit(AnalysisRequest(nmesh=32, npart=1000,
                                      deadline_s=5.0))
    res = region.wait(t, timeout=10)
    summary = region.summary()
    region.shutdown()
    assert res.status == EVICTED
    assert summary['qos']['starved'] == 1
    assert summary['lost'] == 0


# ---------------------------------------------------------------------------
# the router verdict grammar

def _two_fleets(**kw):
    return [Fleet('f0', _FakeServer(**kw)),
            Fleet('f1', _FakeServer(**kw))]


def test_router_affinity_and_spill_verdicts():
    fleets = _two_fleets()
    router = RegionRouter(fleets, spill_depth=2)
    req = AnalysisRequest(nmesh=64, npart=100000, seed=1)
    ai = affinity(req, 1, 2)
    v = router.route(req)
    assert v == {'code': 'affinity', 'fleet': 'f%d' % ai, 'depth': 0}
    # pile queue onto the affinity fleet: structured spill to the
    # least-loaded one
    fleets[ai].server.queued = 10
    v = router.route(req)
    assert v['code'] == 'spill'
    assert v['fleet'] == 'f%d' % (1 - ai)
    assert v['from'] == 'f%d' % ai
    assert v['from_depth'] == 10 and v['depth'] == 0
    # both equally deep: no spill that doesn't help
    fleets[1 - ai].server.queued = 10
    assert router.route(req)['code'] == 'affinity'


def test_router_dead_fleet_and_no_fleet():
    fleets = _two_fleets()
    router = RegionRouter(fleets)
    req = AnalysisRequest(nmesh=64, npart=100000, seed=1)
    ai = affinity(req, 1, 2)
    fleets[ai].server.accepting = False
    v = router.route(req)
    assert v['code'] == 'rerouted_dead'
    assert v['fleet'] == 'f%d' % (1 - ai) and v['from'] == 'f%d' % ai
    fleets[1 - ai].server.accepting = False
    v = router.route(req)
    assert v['code'] == 'no_fleet' and v['fleets'] == 2


def test_router_catalog_home_stickiness(tmp_path):
    path = str(tmp_path / 'survey.bin')
    np.arange(12, dtype='f4').tofile(path)
    ref = {'path': path, 'format': 'binary',
           'columns': {'Position': 'Position'},
           'options': {'dtype': [('Position', ('f4', 3))]}}
    fleets = _two_fleets()
    router = RegionRouter(fleets, spill_depth=2)
    req = AnalysisRequest(nmesh=32, data_ref=ref)
    home = router.route(req)['fleet']
    # later data_ref requests follow the resident catalog even when
    # the home fleet is the deeper one (locality beats a re-ingest)
    router.get(home).server.queued = 50
    v = router.route(AnalysisRequest(nmesh=32, data_ref=ref))
    assert v == {'code': 'catalog_home', 'fleet': home}
    # a dead home falls back to hash placement (and re-homes)
    router.get(home).server.accepting = False
    v = router.route(AnalysisRequest(nmesh=32, data_ref=ref))
    assert v['code'] != 'catalog_home'
    assert v['fleet'] != home


# ---------------------------------------------------------------------------
# the region front door: memoization, followers, the verified stamp

def test_region_cache_hit_and_singleflight_follower(tmp_path):
    server = _FakeServer()
    region = Region([('a', server)],
                    result_cache=ResultCache(str(tmp_path)))
    req = AnalysisRequest(nmesh=32, npart=1000, seed=3,
                          request_id='lead')
    r1 = region.wait(region.submit(req), timeout=10)
    assert r1.ok and len(server.submitted) == 1
    # sequential repeat: a genuine disk hit, zero fleet submissions
    twin = AnalysisRequest(nmesh=32, npart=1000, seed=3,
                           request_id='repeat', priority=2)
    r2 = region.wait(region.submit(twin), timeout=10)
    assert r2.ok and len(server.submitted) == 1
    assert r2.events[0]['kind'] == 'result_cache'
    assert np.array_equal(np.asarray(r2.y), np.asarray(r1.y))
    summary = region.summary()
    assert summary['result_cache']['hits'] == 1
    assert summary['routed']['result_cache'] == 1
    # concurrent twins: followers ride the leader's single execution
    lead = region.submit(AnalysisRequest(nmesh=32, npart=1000,
                                         seed=77, request_id='c0'))
    follow = [region.submit(AnalysisRequest(nmesh=32, npart=1000,
                                            seed=77,
                                            request_id='c%d' % i))
              for i in (1, 2)]
    for t in [lead] + follow:
        assert region.wait(t, timeout=10).ok
    assert len(server.submitted) == 2, 'followers must not resubmit'
    summary = region.summary()
    region.shutdown()
    assert summary['routed']['follower'] == 2
    assert summary['lost'] == 0
    assert np.array_equal(np.asarray(region.results['c1'].y),
                          np.asarray(region.results['c0'].y))


def test_region_verified_stamp_contract(tmp_path):
    """verified=True on a hit means — and may ONLY mean — the
    committed execution was shadow-verified."""
    server = _FakeServer(verify=True)
    region = Region([('a', server)],
                    result_cache=ResultCache(str(tmp_path)))
    req = AnalysisRequest(nmesh=32, npart=1000, seed=1)
    assert region.wait(region.submit(req), timeout=10).ok
    hit = region.wait(region.submit(
        AnalysisRequest(nmesh=32, npart=1000, seed=1)), timeout=10)
    region.shutdown()
    assert hit.events[0] == {'kind': 'result_cache',
                             'digest': hit.events[0]['digest'],
                             'verified': True}
    # an unverified execution commits verified=False and serves as
    # such
    server2 = _FakeServer(verify=False)
    region2 = Region([('b', server2)],
                     result_cache=ResultCache(str(tmp_path / 'u')))
    assert region2.wait(region2.submit(
        AnalysisRequest(nmesh=32, npart=1000, seed=2)), timeout=10).ok
    hit2 = region2.wait(region2.submit(
        AnalysisRequest(nmesh=32, npart=1000, seed=2)), timeout=10)
    summary = region2.summary()
    region2.shutdown()
    assert hit2.events[0]['verified'] is False
    assert summary['result_cache']['unverified_as_verified'] == 0


def test_region_stamp_corruption_is_ledgered(tmp_path):
    """The chaos rule region.result.stamp flips an unverified hit's
    stamp to verified; the region must LEDGER the forgery
    (unverified_as_verified — the doctor's FAIL number), proving CI
    can catch a stamp-integrity bug."""
    server = _FakeServer(verify=False)
    with nbodykit_tpu.set_options(
            faults='region.result.stamp@1:corrupt'):
        region = Region([('a', server)],
                        result_cache=ResultCache(str(tmp_path)))
        assert region.wait(region.submit(
            AnalysisRequest(nmesh=32, npart=1000, seed=4)),
            timeout=10).ok
        hit = region.wait(region.submit(
            AnalysisRequest(nmesh=32, npart=1000, seed=4)),
            timeout=10)
        summary = region.summary()
        region.shutdown()
    assert hit.events[0]['verified'] is True         # the forgery
    assert summary['result_cache']['unverified_as_verified'] == 1


# ---------------------------------------------------------------------------
# elastic grow

def test_grow_repartitions_and_stamps_manifest(tmp_path):
    store = FleetCheckpointStore(str(tmp_path))
    full = np.arange(24.0).reshape(6, 4)
    for r, piece in enumerate(np.array_split(full, 2, axis=0)):
        store.save_shard('sim', 1, r, 2, {'rep': 7},
                         arrays={'field': piece})
    store.seal('sim', 1, nranks=2, rank=0)
    man0 = store.latest_manifest('sim')
    assert 'reformed_from' not in man0   # a plain seal is unstamped
    info = grow(store, 'sim', 3)
    assert info['reformed_from'] == 2 and info['reformed_to'] == 3
    man = store.latest_manifest('sim')
    assert man['nranks'] == 3
    assert man['reformed_from'] == 2 and man['reformed_to'] == 3
    # the grown shards reassemble to the exact original field, and
    # the carried user state survives
    shards = [store.store.load(store.shard_key('sim', man['seq'], r))
              for r in range(3)]
    assert all(s is not None for s in shards)
    assert np.array_equal(
        reassemble([arrays for _, arrays in shards])['field'], full)
    assert shards[0][0]['user'] == {'rep': 7}
    # the reformed stamps are hash-covered: forging one voids the
    # manifest
    path = store._manifest_path('sim', man['seq'])
    forged = json.load(open(path))
    forged['reformed_from'] = 99
    with open(path, 'w') as f:
        json.dump(forged, f)
    assert store.manifest('sim', man['seq']) is None
    # growing from nothing is a first seal, not a re-formation
    with pytest.raises(RuntimeError):
        grow(store, 'never-sealed', 4)


def test_region_join_seals_membership(tmp_path):
    store = FleetCheckpointStore(str(tmp_path))
    region = Region([('f0', _FakeServer()), ('f1', _FakeServer())],
                    checkpoint=store)
    info = region.join(_FakeServer(), name='f2')
    summary = region.summary()
    region.shutdown()
    assert info['reformed_from'] == 2 and info['reformed_to'] == 3
    assert summary['fleet_count'] == 3
    assert summary['elastic']['joins'] == 1
    man = store.latest_manifest('region')
    assert man['nranks'] == 3
    assert man['reformed_from'] == 2 and man['reformed_to'] == 3
    shard = store.store.load(store.shard_key('region', man['seq'], 0))
    assert shard[0]['user']['fleets'] == ['f0', 'f1', 'f2']
    # a second join stamps 3 -> 4 at the next seq
    assert seal_join(store, 'region', {'fleets': 4 * ['x']},
                     new_nranks=4,
                     reformed_from=3)['reformed_to'] == 4
    assert store.latest_manifest('region')['reformed_from'] == 3


def test_region_routes_around_dead_fleet_after_join():
    a, b = _FakeServer(), _FakeServer()
    region = Region([('f0', a), ('f1', b)])
    a.accepting = False
    t = region.submit(AnalysisRequest(nmesh=32, npart=1000, seed=9))
    res = region.wait(t, timeout=10)
    summary = region.summary()
    region.shutdown()
    assert res.ok
    assert b.submitted and not a.submitted
    assert summary['lost'] == 0


# ---------------------------------------------------------------------------
# the data_steal_grace_s satellite

def test_data_steal_grace_resolution(monkeypatch):
    from nbodykit_tpu.serve.server import _resolve_data_steal_grace
    monkeypatch.delenv('NBKIT_DATA_STEAL_GRACE_S', raising=False)
    assert _resolve_data_steal_grace('auto') \
        == AnalysisServer.DATA_STEAL_GRACE_S
    assert _resolve_data_steal_grace(0.25) == 0.25
    assert _resolve_data_steal_grace(0) == 0.0
    assert _resolve_data_steal_grace('2.5') == 2.5
    monkeypatch.setenv('NBKIT_DATA_STEAL_GRACE_S', '3.5')
    assert _resolve_data_steal_grace('auto') == 3.5
    assert _resolve_data_steal_grace(0.5) == 0.5   # option wins
    for bad in (-1.0, float('nan'), float('inf'), 'soon'):
        with pytest.raises(ValueError):
            _resolve_data_steal_grace(bad)
    monkeypatch.setenv('NBKIT_DATA_STEAL_GRACE_S', 'nonsense')
    with pytest.raises(ValueError):
        _resolve_data_steal_grace('auto')
    with pytest.raises(KeyError):
        nbodykit_tpu.set_options(data_steal_grace=1.0)  # typo'd name
    # 'auto' stays a value here: the environment answers it
    with nbodykit_tpu.set_options(data_steal_grace_s='auto'):
        assert _global_options['data_steal_grace_s'] == 'auto'


def test_server_resolves_data_steal_grace_option():
    with nbodykit_tpu.set_options(data_steal_grace_s=0.125):
        with use_mesh(cpu_mesh(1)):
            srv = AnalysisServer(per_task=1)
    try:
        assert srv.data_steal_grace_s == 0.125
        assert srv.load()['accepting'] is True
    finally:
        srv.shutdown()
    assert srv.load()['accepting'] is False


# ---------------------------------------------------------------------------
# trace synthesis

def test_generate_region_trace_deterministic_with_repeats():
    a = generate_region_trace(120, seed=5, join_at=0.5)
    b = generate_region_trace(120, seed=5, join_at=0.5)
    assert len(a) == 121        # 120 items + the join event
    assert [sorted(i) for i in a] == [sorted(i) for i in b]
    assert sum(1 for i, x in enumerate(a) if 'event' in x) == 1
    assert a[60] == {'event': 'join'}
    reqs = [x for x in a if 'request' in x]
    ids = [x['request'].request_id for x in reqs]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)
    for x, y in zip(reqs, b[:60] + b[61:]):
        assert x['tenant'] == y['tenant']
        assert x['request'].to_dict() == y['request'].to_dict()
    # per-tenant repeat slices: some request re-issues an exact
    # earlier realization of the SAME tenant
    seen = {}
    repeats = 0
    for x in reqs:
        key = (x['request'].algorithm, x['request'].nmesh,
               x['request'].npart, x['request'].seed)
        repeats += key in seen and seen[key] == x['tenant']
        seen.setdefault(key, x['tenant'])
    assert repeats > 0
    # the bulk tenant self-declares priority 2 on every request
    bulk = [x for x in reqs if x['tenant'] == 'bulk-sweep']
    assert bulk and all(x['request'].priority == 2 for x in bulk)
    tenants = {x['tenant'] for x in reqs}
    assert tenants <= {'interactive-a', 'interactive-b',
                       'bulk-sweep'} and len(tenants) == 3


def test_replay_region_fires_join_event(tmp_path):
    from nbodykit_tpu.serve import replay_region
    region = Region([('f0', _FakeServer())],
                    result_cache=ResultCache(str(tmp_path)))
    trace = generate_region_trace(20, seed=2, deadline_s=30.0,
                                  join_at=0.4)
    joined = []
    tickets = replay_region(
        region, trace,
        on_join=lambda reg: joined.append(reg.join(_FakeServer())))
    summary = region.summary()
    region.shutdown()
    assert len(joined) == 1
    assert joined[0] == {'fleet': 'fleet-1', 'reformed_from': 1,
                         'reformed_to': 2,
                         'rehomed': joined[0]['rehomed']}
    assert len(tickets) == 20
    assert summary['lost'] == 0
    assert summary['resolved'] == 20
    assert summary['elastic']['joins'] == 1
    # the repeat slice produced real memoization traffic
    assert summary['result_cache']['hits'] \
        + summary['routed'].get('follower', 0) > 0


# ---------------------------------------------------------------------------
# one real end-to-end pass (everything above uses fakes)

def test_region_e2e_real_server_bit_identical_hit():
    with use_mesh(cpu_mesh(1)):
        srv = AnalysisServer(per_task=1, max_queue=8)
    import tempfile
    region = Region([('a', srv)],
                    result_cache=ResultCache(tempfile.mkdtemp()))
    req = AnalysisRequest(nmesh=32, npart=2000, seed=11,
                          deadline_s=600.0, request_id='real-0')
    r1 = region.wait(region.submit(req), timeout=300)
    assert r1 is not None and r1.ok, r1
    r2 = region.wait(region.submit(
        AnalysisRequest(nmesh=32, npart=2000, seed=11,
                        deadline_s=600.0, request_id='real-1')),
        timeout=60)
    summary = region.summary()
    region.shutdown()
    assert r2.ok and r2.events[0]['kind'] == 'result_cache'
    # the memoized spectrum is bit-identical to the computed one
    assert np.asarray(r2.y).tobytes() == np.asarray(r1.y).tobytes()
    assert np.asarray(r2.x).tobytes() == np.asarray(r1.x).tobytes()
    assert summary['result_cache']['hits'] == 1
    assert summary['lost'] == 0


# ---------------------------------------------------------------------------
# regress / doctor posture

def test_region_summary_reads_committed_round(tmp_path):
    from nbodykit_tpu.diagnostics.regress import (build_history,
                                                  region_summary,
                                                  render_regress)
    rec = {'metric': 'regiontrace_n40', 'unit': 's', 'value': 1.5,
           'requests': 40, 'fleets': 2, 'fleet_count': 3,
           'completed': 40, 'rejected': 0, 'evicted': 0, 'lost': 0,
           'result_hits': 9, 'hit_rate': 0.18, 'cache_corrupt': 0,
           'cache_bit_identical': True, 'unverified_as_verified': 0,
           'spills': 6, 'joins': 1, 'reformed_from': 2,
           'reformed_to': 3, 'throttled': 2, 'starved': 0,
           'interactive_p50_s': 1.1, 'interactive_p99_s': 1.5,
           'measured_at': '2026-08-06T00:00:00Z'}
    (tmp_path / 'BENCH_r01.json').write_text(json.dumps(
        {'cmd': 'bench --region-trace 40 2', 'parsed': rec}))
    reg = region_summary(str(tmp_path))
    assert reg is not None and reg['round'] == 'BENCH_r01.json'
    assert reg['lost'] == 0 and reg['result_hits'] == 9
    assert reg['reformed_from'] == 2 and reg['reformed_to'] == 3
    assert reg['unverified_as_verified'] == 0
    history = build_history(str(tmp_path), write=False)
    assert history['region']['metric'] == 'regiontrace_n40'
    line = next(l for l in render_regress(history).splitlines()
                if l.strip().startswith('region:'))
    assert '40 req over 3 fleet(s)' in line
    assert 'fleet re-formed 2 -> 3' in line
    assert '0 lost' in line


def test_region_summary_none_without_round(tmp_path):
    from nbodykit_tpu.diagnostics.regress import region_summary
    assert region_summary(str(tmp_path)) is None
