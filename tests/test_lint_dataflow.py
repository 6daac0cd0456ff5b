"""The interprocedural dataflow engine (nbkl v2): NBK103
collective-order deadlock detection and the NBK5xx static
HBM/donation analysis — seeded positives and negatives, the symbolic
peak model against the documented dfft buffer contracts, the baseline
roundtrip for the new codes, the --stats / --memory-report CLI
surfaces, and the doctor's NBK5xx <-> device-watermark cross-link.

Pure-host AST tests except the CLI subprocess and doctor checks.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from nbodykit_tpu import lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_str(src, select=None, memory_config=None):
    return lint.lint_source(
        'fixture.py', textwrap.dedent(src),
        project_constants={'AXIS': 'dev'}, select=select,
        memory_config=memory_config)


def codes(findings):
    return [f.code for f in findings]


# ---------------------------------------------------------------------------
# NBK103 — collective-order deadlock detection

def test_nbk103_rank_divergent_sequences():
    # BOTH arms emit collectives, in different orders — NBK102 has no
    # opinion (no arm skips them), NBK103 must still flag the order
    fs = lint_str("""
    import jax

    def step(x):
        rank = jax.process_index()
        if rank == 0:
            x = jax.lax.psum(x, 'dev')
            x = jax.lax.all_gather(x, 'dev')
        else:
            x = jax.lax.all_gather(x, 'dev')
            x = jax.lax.psum(x, 'dev')
        return x
    """, select=['NBK103'])
    assert codes(fs) == ['NBK103']
    assert 'rank' in fs[0].message


def test_nbk103_exception_path_between_collectives():
    fs = lint_str("""
    import jax

    def pipeline(x, n):
        x = jax.lax.psum(x, 'dev')
        if n < 0:
            raise ValueError('bad shard')
        return jax.lax.all_to_all(x, 'dev', 0, 0)
    """, select=['NBK103'])
    assert codes(fs) == ['NBK103']
    assert 'strands its peers' in fs[0].message


def test_nbk103_matched_sequences_negative():
    # rank-dependent VALUES but identical collective sequences on
    # both arms: every rank emits the same program — clean
    fs = lint_str("""
    import jax

    def step(x):
        rank = jax.process_index()
        if rank == 0:
            x = jax.lax.psum(x * 2, 'dev')
        else:
            x = jax.lax.psum(x, 'dev')
        return x
    """, select=['NBK103'])
    assert fs == []


def test_nbk103_unconditional_raise_is_clean():
    # validation BEFORE the first collective is the recommended
    # pattern and must not fire
    fs = lint_str("""
    import jax

    def pipeline(x, n):
        if n < 0:
            raise ValueError('bad input')
        x = jax.lax.psum(x, 'dev')
        return jax.lax.all_to_all(x, 'dev', 0, 0)
    """, select=['NBK103'])
    assert fs == []


def test_nbk103_interprocedural_through_helper():
    # the collective hides in a helper: NBK103's summaries splice the
    # callee sequence into the rank-gated branch
    fs = lint_str("""
    import jax

    def reduce_all(x):
        return jax.lax.psum(x, 'dev')

    def run(x):
        rank = jax.process_index()
        if rank == 0:
            x = reduce_all(x)
        return x
    """, select=['NBK103'])
    assert codes(fs) == ['NBK103']


def test_nbk103_cross_module(tmp_path):
    # rank gate in one module, collective in another — beyond
    # NBK102's same-module reach
    pkg = tmp_path / 'pkg'
    pkg.mkdir()
    (pkg / 'helpers.py').write_text(textwrap.dedent("""
        import jax

        def reduce_all(x):
            return jax.lax.psum(x, 'dev')
    """))
    (pkg / 'driver.py').write_text(textwrap.dedent("""
        import jax
        from helpers import reduce_all

        def run(x):
            rank = jax.process_index()
            if rank == 0:
                x = reduce_all(x)
            return x
    """))
    fs = lint.lint_paths([str(pkg)], select=['NBK103'])
    assert codes(fs) == ['NBK103']
    assert fs[0].path.endswith('driver.py')


def test_nbk103_data_divergence_in_traced_code():
    fs = lint_str("""
    import jax

    @jax.jit
    def body(x):
        if x.sum() > 0:
            x = jax.lax.psum(x, 'dev')
        return x
    """, select=['NBK103'])
    assert codes(fs) == ['NBK103']
    assert 'traced-data' in fs[0].message


_PROGRAM_PAIR = """
import functools
import jax
from diagnostics import instrumented_jit

@functools.lru_cache(maxsize=8)
def _programs(mesh):
    def local(v):
        return jax.lax.psum(v, 'dev')

    sharded = jax.shard_map(local, mesh=mesh, in_specs=None,
                            out_specs=None)

    def exchange(v):
        return sharded(v)

    return exchange, instrumented_jit(exchange, label='exchange')

@functools.lru_cache(maxsize=8)
def _other_programs(mesh):
    def local(v):
        return jax.lax.all_gather(v, 'dev')

    raw = jax.shard_map(local, mesh=mesh, in_specs=None, out_specs=None)
    return raw, instrumented_jit(raw, label='other')

def run(x, mesh, eager, n):
    raw, jitted = _programs(mesh)
    oraw, ojit = _other_programs(mesh)
    x = (jitted if eager else raw)(x)
    if n < 0:
        raise ValueError('bad shard')
    return (%s)(x)
"""


def test_nbk103_follows_a_cached_program_pair():
    # the idiom of dfft._slab_programs, exchange._exchange_programs and
    # pmesh._slab_paint_programs: a builder returns (raw, jit) over one
    # body and the caller picks by a conditional.  The collectives
    # behind the handle count, so the raise between two such calls is
    # a finding, in the builder's own module too (``exchange`` is a
    # name of the builder's scope)
    fs = lint_str(_PROGRAM_PAIR % 'jitted if eager else raw',
                  select=['NBK103'])
    assert codes(fs) == ['NBK103']
    assert 'strands its peers' in fs[0].message


def test_nbk103_arms_of_different_programs_stay_unresolved():
    # a pick between two different bodies reaches no one def: the call
    # resolves to nothing and the lint stays silent rather than guess
    fs = lint_str(_PROGRAM_PAIR % 'ojit if eager else raw',
                  select=['NBK103'])
    assert fs == []


def test_nbk103_follows_a_cached_single_program():
    # the idiom of convpower/fkp.py:_ell_program: a builder whose one
    # return is one wrapped body, bound to a name and called.  The
    # raise between two such calls is a finding
    fs = lint_str("""
import functools
import jax
from diagnostics import instrumented_jit

@functools.lru_cache(maxsize=8)
def _program(ell, mesh):
    def prog(v):
        if ell == 0:
            return v
        return jax.lax.psum(v, 'dev')

    return instrumented_jit(prog, label='ell')

def run(x, mesh, n):
    def term(ell, v):
        prog = _program(ell, mesh)
        return prog(v)

    x = term(0, x)
    if n < 0:
        raise ValueError('bad shard')
    return term(2, x)
""", select=['NBK103'])
    assert codes(fs) == ['NBK103']
    assert 'strands its peers' in fs[0].message


def _collective_summaries():
    from nbodykit_tpu.lint.collectives import analysis_for
    from nbodykit_tpu.lint.walker import build_project
    project = build_project([os.path.join(REPO, 'nbodykit_tpu')])[0]
    analysis = analysis_for(project)
    out = {}
    for ctx, fn in project.functions():
        name = getattr(fn, 'name', None)
        if name is not None:
            out.setdefault((ctx.module, name), []).append(
                analysis.summary_of(fn))
    return out


@pytest.fixture(scope='module')
def collective_summaries():
    return _collective_summaries()


@pytest.mark.parametrize('module, function, token', [
    ('nbodykit_tpu.parallel.exchange', 'exchange_by_dest', 'all_to_all'),
    ('nbodykit_tpu.parallel.dfft', '_slab_run', 'all_to_all'),
    ('nbodykit_tpu.parallel.dfft', '_pencil_run', 'all_to_all'),
    # paint's and readout's: the exchange, then the halo
    ('nbodykit_tpu.pmesh', 'attempt', 'all_to_all'),
    ('nbodykit_tpu.pmesh', 'attempt', 'ppermute'),
    # the survey call's per-ell programs, behind ``_ell_program``
    ('nbodykit_tpu.algorithms.convpower.fkp', 'term', 'all_to_all'),
])
def test_nbk103_sees_the_collectives_of_the_slab_path(
        collective_summaries, module, function, token):
    # the eager multi-device paint and FFTs run as cached programs; the
    # deadlock lint has to see through each handle to its shard_map
    # body, or a rank-dependent edit around them goes unflagged
    summaries = collective_summaries[(module, function)]
    # (None is "too many paths to track", which the lint reads as
    # no collective at all)
    assert all(s is not None and any(token in seq for seq in s)
               for s in summaries), summaries


# ---------------------------------------------------------------------------
# NBK501/502 — donation analysis

_DONATION_HEADER = """
    import jax
    import jax.numpy as jnp

    def power(field):
        return jnp.abs(field) ** 2
"""


def test_nbk501_missed_donation():
    fs = lint_str(_DONATION_HEADER + """
    fast_power = jax.jit(power)

    def run(pm, pos):
        field = pm.paint(pos)
        p3 = fast_power(field)
        return p3.sum()
    """, select=['NBK5'])
    assert codes(fs) == ['NBK501']
    assert "'field'" in fs[0].message
    assert 'donate_argnums=(0,)' in fs[0].hint


def test_nbk501_silent_when_value_still_needed():
    # the field is read after the call: donation would be wrong, so
    # NBK501 must NOT ask for it
    fs = lint_str(_DONATION_HEADER + """
    fast_power = jax.jit(power)

    def run(pm, pos):
        field = pm.paint(pos)
        p3 = fast_power(field)
        return p3.sum() + field.sum()
    """, select=['NBK5'])
    assert fs == []


def test_nbk502_donated_but_held_live():
    fs = lint_str(_DONATION_HEADER + """
    fast_power = jax.jit(power, donate_argnums=(0,))

    def run(pm, pos):
        field = pm.paint(pos)
        p3 = fast_power(field)
        return p3.sum() + field.sum()
    """, select=['NBK5'])
    assert codes(fs) == ['NBK502']
    assert 'defeats the aliasing' in fs[0].message


def test_nbk502_loop_reuse_of_donated_buffer():
    # donated inside a loop while the buffer was built outside it:
    # iteration 2 reads a buffer iteration 1 donated away
    fs = lint_str(_DONATION_HEADER + """
    fast_power = jax.jit(power, donate_argnums=(0,))

    def run(pm, pos, reps):
        field = pm.paint(pos)
        out = []
        for _ in range(reps):
            out.append(fast_power(field))
        return out
    """, select=['NBK5'])
    assert codes(fs) == ['NBK502']


def test_nbk502_donated_accumulator_is_clean():
    # the dfft donated-accumulator idiom: y = upd(y, ...) rebinds the
    # handle every iteration — exactly one owner, no finding
    fs = lint_str("""
    import jax
    import jax.numpy as jnp

    def upd(dst, i):
        return dst.at[i].set(i)

    fast_upd = jax.jit(upd, donate_argnums=(0,))

    def run(pm, pos, n):
        y = pm.paint(pos)
        for i in range(n):
            y = fast_upd(y, i)
        return y
    """, select=['NBK5'])
    assert fs == []


def test_donation_clean_chain_negative():
    fs = lint_str(_DONATION_HEADER + """
    fast_power = jax.jit(power, donate_argnums=(0,))

    def run(pm, pos):
        field = pm.paint(pos)
        p3 = fast_power(field)
        return p3.sum()
    """, select=['NBK5'])
    assert fs == []


def test_labeled_taint_does_not_leak_through_timers():
    # a helper returning wall-clock floats must not inherit the mesh
    # size of its field argument (the labeled-taint regression that
    # motivated ret_params)
    fs = lint_str(_DONATION_HEADER + """
    import time

    def timeit(fn, arg):
        t0 = time.time()
        fn(arg)
        return time.time() - t0

    fast_power = jax.jit(power)

    def run(pm, pos):
        field = pm.paint(pos)
        dt = timeit(fast_power, field)
        dt2 = dt * 2
        return dt2
    """, select=['NBK5'])
    # 'dt' is not mesh-sized, so no donation findings are raised on
    # later uses of it; the field itself is consumed by an untracked
    # callee (timeit) so no NBK501 either
    assert fs == []


# ---------------------------------------------------------------------------
# NBK503 — symbolic peak vs the memory_plan budget

def test_nbk503_symbolic_peak_over_budget():
    config = lint.make_config(1024, dtype_bytes=4, hbm_bytes=16e9)
    fs = lint_str("""
    import jax.numpy as jnp

    def stage_chain(pm, pos):
        a = pm.paint(pos)
        b = pm.r2c(a)
        c = b * 2.0
        d = jnp.abs(c) ** 2
        return a.sum() + d.sum()
    """, select=['NBK503'], memory_config=config)
    assert codes(fs) == ['NBK503']
    assert 'memory_plan budget' in fs[0].message


def test_nbk503_silent_without_config_and_under_budget():
    src = """
    import jax.numpy as jnp

    def stage_chain(pm, pos):
        a = pm.paint(pos)
        b = pm.r2c(a)
        c = b * 2.0
        d = jnp.abs(c) ** 2
        return a.sum() + d.sum()
    """
    assert lint_str(src, select=['NBK503']) == []
    small = lint.make_config(256, dtype_bytes=4, hbm_bytes=16e9)
    assert lint_str(src, select=['NBK503'], memory_config=small) == []


def test_nbk503_shell_filtered_fields_are_mesh_taint():
    """ISSUE 20 satellite: each per-shell filtered field of the
    bispectrum estimator (algorithms/bispectrum.py) is a full real
    mesh, so ``shell_filtered_field`` must be a recognized producer.
    The fixture pair: the streaming triple-product (3 shell fields
    live — the memory_plan(workload='bispectrum') contract) FITS the
    declared budget; naively holding a field per shell EXCEEDS it —
    if the producer classification regresses, the second assertion
    catches the silent under-report."""
    src = """
    import jax.numpy as jnp

    def triple_streams(pm, cplx):
        d1 = shell_filtered_field(pm, cplx, 1, 4)
        d2 = shell_filtered_field(pm, cplx, 4, 9)
        d3 = shell_filtered_field(pm, cplx, 9, 16)
        return (d1 * d2 * d3).sum()

    def shells_exceed(pm, cplx):
        d0 = shell_filtered_field(pm, cplx, 1, 4)
        d1 = shell_filtered_field(pm, cplx, 4, 9)
        d2 = shell_filtered_field(pm, cplx, 9, 16)
        d3 = shell_filtered_field(pm, cplx, 16, 25)
        d4 = shell_filtered_field(pm, cplx, 25, 36)
        d5 = shell_filtered_field(pm, cplx, 36, 49)
        return (d0 * d1 * d2 * d3 * d4 * d5).sum()
    """
    # 1 unit = 4.29 GB; budget 0.85*28 GB = 23.8 GB: the streaming
    # triple (2 live + 3 internal = 5 units = 21.5 GB) fits, the
    # per-shell pile-up (5 live + 3 internal = 8 units = 34.4 GB)
    # does not
    config = lint.make_config(1024, dtype_bytes=4, hbm_bytes=28e9)
    fs = lint_str(src, select=['NBK503'], memory_config=config)
    assert codes(fs) == ['NBK503']
    assert 'shells_exceed' in fs[0].message
    assert 'triple_streams' not in ' '.join(f.message for f in fs)


def test_nbk503_grad_call_site_prices_the_backward_pass():
    """ISSUE 19 satellite: ``jax.grad(f)`` holds f's intermediates as
    residuals for the backward pass, so a grad call site must add f's
    internal peak once more.  The fixture pair: the forward-only
    pipeline FITS the declared budget; the identical pipeline under
    ``jax.grad`` EXCEEDS it — if the grad accounting regresses to
    zero, the second assertion catches the silent under-report."""
    src = """
    import jax
    import jax.numpy as jnp

    def loss(pm, x):
        a = pm.paint(x)
        b = pm.r2c(a)
        return jnp.abs(b).sum()

    def forward_fits(pm):
        w = pm.generate_whitenoise(0)
        return loss(pm, w)

    def grad_exceeds(pm):
        w = pm.generate_whitenoise(0)
        g = jax.grad(loss, argnums=1)(pm, w)
        return g.sum()
    """
    # 1 unit = 4.29 GB; budget 0.85*28 GB = 23.8 GB: the forward
    # pipeline (5 units = 21.5 GB) fits, the grad pipeline (forward
    # + residuals + live leaves = 10 units = 42.9 GB) does not
    config = lint.make_config(1024, dtype_bytes=4, hbm_bytes=28e9)
    fs = lint_str(src, select=['NBK503'], memory_config=config)
    assert codes(fs) == ['NBK503']
    assert 'grad_exceeds' in fs[0].message
    # the named-wrapper spelling (vg = jit(value_and_grad(f)); vg(x))
    # prices the same residuals — not only the immediate form
    named = """
    import jax
    import jax.numpy as jnp

    def loss(pm, x):
        a = pm.paint(x)
        b = pm.r2c(a)
        return jnp.abs(b).sum()

    def grad_named(pm):
        w = pm.generate_whitenoise(0)
        vg = jax.jit(jax.value_and_grad(loss, argnums=1))
        val, g = vg(pm, w)
        return g.sum()
    """
    fs2 = lint_str(named, select=['NBK503'], memory_config=config)
    assert codes(fs2) == ['NBK503']
    assert 'grad_named' in fs2[0].message
    # (11 units for the named form: the value_and_grad closure object
    # is a live leaf alongside the residuals)


# ---------------------------------------------------------------------------
# the symbolic peak model against the documented dfft buffer contracts

def _project_summaries(paths):
    from nbodykit_tpu.lint.sizes import analysis_for
    project, parse = lint.build_project(paths)
    assert parse == []
    an = analysis_for(project)
    out = {}
    import ast
    for ctx, fn in project.functions():
        if isinstance(fn, ast.Lambda):
            continue
        out[(ctx.canonical, fn.name)] = an.summary_of(fn)
    return out


def test_pencil_stages_summarize_cleanly():
    """ISSUE 9 satellite: the pencil drivers' inner/outer all_to_all
    pair must stay legible to the NBK103 dataflow engine — each stage
    closure of _pencil_programs (forward and inverse) summarizes to
    exactly one all_to_all token, nothing in dfft.py degrades to the
    VARIED sentinel, and the module lints clean for NBK103."""
    import ast
    from nbodykit_tpu.lint.collectives import analysis_for, VARIED
    path = os.path.join(REPO, 'nbodykit_tpu', 'parallel', 'dfft.py')
    project, parse = lint.build_project([path])
    assert parse == []
    an = analysis_for(project)
    stages = []
    for ctx, fn in project.functions():
        summ = an.summary_of(fn)
        name = getattr(fn, 'name', '<lambda>')
        assert summ is not VARIED, \
            '%s degraded to VARIED — the deadlock comparisons go ' \
            'silent over the pencil transposes' % name
        if name in ('stage1', 'stage2'):
            stages.append((name, summ))
    # two pencil programs (forward + inverse), two stages each, one
    # all_to_all per stage: the inner ('y') and outer ('x') transposes.
    # The integrity-guarded variant adds a psum (fold checksum) after
    # the wire — still one deterministic collective program per arm.
    allowed = (frozenset({('all_to_all',)}),
               frozenset({('all_to_all',), ('all_to_all', 'psum')}))
    assert len(stages) == 4
    for name, summ in stages:
        assert summ in allowed, (name, summ)
    findings = lint.lint_paths([path], select=['NBK103'])
    assert [f for f in findings if f.code == 'NBK103'] == []


def test_dfft_lowmem_contract_is_machine_checked():
    """PR 4 documented the lowmem drivers at ~2 full-mesh buffers and
    the dist_* entry points at ~3 (driver's 2 + the caller-held input
    ref, which the model books to the caller).  The symbolic peak
    model now derives those numbers from the source — the contract is
    machine-checked, not prose."""
    s = _project_summaries([os.path.join(REPO, 'nbodykit_tpu',
                                         'parallel', 'dfft.py')])
    dfft = 'nbodykit_tpu/parallel/dfft.py'
    for driver in ('rfftn_single_lowmem', 'irfftn_single_lowmem',
                   'fftn_c2c_single_lowmem'):
        assert s[(dfft, driver)].peak == 2.0, driver
    # entry points: 2 units internal; the caller's live input ref is
    # the documented third buffer (params are booked to callers)
    assert s[(dfft, 'dist_rfftn')].peak == 2.0
    assert s[(dfft, 'dist_irfftn')].peak == 2.0


def test_bench_fused_peak_vs_lowmem_driver():
    """What the symbolic peak model says of the 1024-cubed config:
    bench.py's fused pipeline (power3d) books 4+ full-mesh units, over
    the 0.85 x 16 GB budget, while the donated lowmem FFT driver peaks
    at 2 units, inside it.  The model errs high on purpose: libtpu
    compiles the fused program at 1024-cubed with 10.39 GB of
    temporaries (bench.py:run_config), so bench runs it fused."""
    s = _project_summaries([os.path.join(REPO, 'bench.py'),
                            os.path.join(REPO, 'nbodykit_tpu',
                                         'parallel', 'dfft.py')])
    fused = s[('bench.py', 'power3d')].peak
    lowmem = s[('nbodykit_tpu/parallel/dfft.py',
                'rfftn_single_lowmem')].peak
    assert fused >= 4.0 and lowmem == 2.0
    config = lint.make_config(1024)
    from nbodykit_tpu.lint.sizes import unit_bytes
    assert lowmem * unit_bytes(config) <= config.budget_bytes
    assert fused * unit_bytes(config) > config.budget_bytes


def test_memory_report_rows_and_budget():
    config = lint.make_config(1024)
    project, _ = lint.build_project(
        [os.path.join(REPO, 'bench.py'),
         os.path.join(REPO, 'nbodykit_tpu', 'parallel', 'dfft.py')])
    report = lint.memory_report(project, config)
    rows = {r['function']: r for r in report['rows']}
    assert rows['power3d']['over_budget'] is True
    assert rows['rfftn_single_lowmem']['over_budget'] is False
    text = lint.render_memory_report(report)
    assert 'OVER BUDGET' in text and 'rfftn_single_lowmem' in text


# ---------------------------------------------------------------------------
# baseline roundtrip for the new codes

def test_baseline_line_drift_roundtrip_new_codes(tmp_path):
    src_v1 = textwrap.dedent("""
    import jax
    import jax.numpy as jnp

    def power(field):
        return jnp.abs(field) ** 2

    fast_power = jax.jit(power)

    def run(pm, pos, n):
        field = pm.paint(pos)
        x = jax.lax.psum(field, 'dev')
        if n < 0:
            raise ValueError('bad')
        x = jax.lax.all_to_all(x, 'dev', 0, 0)
        p3 = fast_power(field)
        return p3
    """)
    findings = lint.lint_source('pkg.py', src_v1,
                                select=['NBK103', 'NBK5'])
    assert sorted(codes(findings)) == ['NBK103', 'NBK501']
    sources = {'pkg.py': src_v1.splitlines()}
    doc = lint.build_baseline(findings, sources=sources)
    path = str(tmp_path / 'baseline.json')
    lint.write_baseline(doc, path)

    # three lines of drift above: both entries still grandfathered
    src_v2 = '# a\n# b\n# c\n' + src_v1
    moved = lint.lint_source('pkg.py', src_v2,
                             select=['NBK103', 'NBK5'])
    assert sorted(codes(moved)) == ['NBK103', 'NBK501']
    new, grand, unused = lint.apply_baseline(
        moved, lint.load_baseline(path),
        sources={'pkg.py': src_v2.splitlines()})
    assert new == [] and len(grand) == 2 and unused == []

    # both fixed: the stale entries surface for pruning
    new, grand, unused = lint.apply_baseline(
        [], lint.load_baseline(path), sources={})
    assert new == [] and grand == [] and len(unused) == 2


# ---------------------------------------------------------------------------
# acceptance: seeded deadlock + donation fixtures through the CLI
# subprocess AND the pytest-gate API path

SEEDED_FIXTURE = textwrap.dedent("""
    import jax
    import jax.numpy as jnp

    def power(field):
        return jnp.abs(field) ** 2

    fast_power = jax.jit(power, donate_argnums=(0,))

    def deadlock(x, n):
        x = jax.lax.psum(x, 'dev')
        if n < 0:
            raise ValueError('bad shard')
        return jax.lax.all_to_all(x, 'dev', 0, 0)

    def held(pm, pos):
        field = pm.paint(pos)
        p3 = fast_power(field)
        return p3.sum() + field.sum()
""")


def test_seeded_fixtures_detected_by_pytest_gate(tmp_path):
    pkg = tmp_path / 'nbodykit_tpu'
    pkg.mkdir()
    (pkg / 'seeded.py').write_text(SEEDED_FIXTURE)
    new, _, _ = lint.run_lint([str(pkg)])
    assert sorted(f.code for f in new) == ['NBK103', 'NBK502']
    assert all(f.path == 'nbodykit_tpu/seeded.py' for f in new)


def test_seeded_fixtures_detected_by_cli(tmp_path):
    fixture = tmp_path / 'seeded.py'
    fixture.write_text(SEEDED_FIXTURE)
    proc = subprocess.run(
        [sys.executable, '-m', 'nbodykit_tpu.lint', str(fixture)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert 'NBK103' in proc.stdout and 'NBK502' in proc.stdout
    # grandfathered, the same invocation gates green
    bl = tmp_path / 'baseline.json'
    subprocess.run(
        [sys.executable, '-m', 'nbodykit_tpu.lint', str(fixture),
         '--write-baseline', str(bl)],
        capture_output=True, text=True, cwd=REPO, check=True)
    proc = subprocess.run(
        [sys.executable, '-m', 'nbodykit_tpu.lint', str(fixture),
         '--baseline', str(bl)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_stats_json(tmp_path):
    fixture = tmp_path / 'seeded.py'
    fixture.write_text(SEEDED_FIXTURE)
    proc = subprocess.run(
        [sys.executable, '-m', 'nbodykit_tpu.lint', str(fixture),
         '--stats'],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    assert data['gate'] == 'FAIL'
    assert data['families']['NBK1']['new'] == 1
    assert data['families']['NBK5']['new'] == 1
    assert data['by_code']['new'] == {'NBK103': 1, 'NBK502': 1}
    assert data['total']['new'] == 2


def test_cli_memory_report(tmp_path):
    proc = subprocess.run(
        [sys.executable, '-m', 'nbodykit_tpu.lint',
         '--memory-report', '--nmesh', '1024', 'bench.py'],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert 'nmesh=1024' in proc.stdout
    assert 'power3d' in proc.stdout
    assert 'OVER BUDGET' in proc.stdout      # the fused pipeline
    # --memory-report without a config is a usage error
    proc = subprocess.run(
        [sys.executable, '-m', 'nbodykit_tpu.lint',
         '--memory-report', 'bench.py'],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 2


def test_rule_catalog_lists_new_codes():
    proc = subprocess.run(
        [sys.executable, '-m', 'nbodykit_tpu.lint', '--list-rules'],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0
    for code in ('NBK103', 'NBK501', 'NBK502', 'NBK503'):
        assert code in proc.stdout


# ---------------------------------------------------------------------------
# regress + doctor integration

def test_regress_records_per_family_counts(tmp_path):
    import shutil
    from nbodykit_tpu.diagnostics.regress import lint_summary

    root = str(tmp_path)
    os.symlink(os.path.join(REPO, 'nbodykit_tpu'),
               os.path.join(root, 'nbodykit_tpu'))
    for extra in ('bench.py',):
        shutil.copy(os.path.join(REPO, extra),
                    os.path.join(root, extra))
    shutil.copy(os.path.join(REPO, 'lint_baseline.json'),
                os.path.join(root, 'lint_baseline.json'))
    summ = lint_summary(root)
    assert summ['new'] == 0
    fams = summ['families']
    # every family axis is present so shrinkage is tracked per family
    for fam in ('NBK1', 'NBK2', 'NBK3', 'NBK4', 'NBK5'):
        assert fam in fams, fams
    # the audited NBK103 entries and the bench NBK202s are baselined
    assert fams['NBK1']['baselined'] >= 2
    assert fams['NBK2']['baselined'] >= 5


def test_doctor_cross_links_watermark_to_nbk5(tmp_path, capsys):
    from nbodykit_tpu.diagnostics import REGISTRY
    from nbodykit_tpu.diagnostics.metrics import REGISTRY as MREG
    from nbodykit_tpu.diagnostics.__main__ import run_doctor

    root = str(tmp_path)
    pkg = tmp_path / 'nbodykit_tpu'
    pkg.mkdir()
    (pkg / 'seeded.py').write_text(SEEDED_FIXTURE)
    # a watermark past half a v5e's HBM, as device_watermarks() would
    # record it after a hot run
    MREG.gauge('device.tpu:0.live_bytes').set(9.5e9)
    try:
        run_doctor(trace=None, root=root)
        out = capsys.readouterr().out
        assert 'memory       WARN' in out
        assert 'NBK502' in out and 'seeded.py' in out
        assert '9.50 GB' in out
    finally:
        REGISTRY.reset()


# ---------------------------------------------------------------------------
# regression: the pre-fix eager _fftn_c2c_single_chunked shape


def test_nbk503_would_have_caught_eager_chunked_fft():
    # dfft.py's _fftn_c2c_single_chunked originally allocated the FULL
    # complex result up front and fori_loop-wrote chunks into it —
    # peak = input + eager output + per-chunk FFT temporaries, a
    # multi-GB regression the 2-buffer rewrite removed.  This fixture
    # freezes that shape: the static peak model must flag it at the
    # documented 1024^3 complex config, and the same code must stay
    # silent where it genuinely fits (512^3).
    src = """
    import jax
    import jax.numpy as jnp

    def fftn_c2c_eager(v, shape_complex):
        x = to_complex_field(v)
        out = jnp.zeros(shape_complex, jnp.complex64)
        def body(i, acc):
            return acc.at[i].set(jnp.fft.fftn(x[i]))
        out = jax.lax.fori_loop(0, 8, body, out)
        return out
    """
    config = lint.make_config(1024, dtype_bytes=8, hbm_bytes=16e9)
    fs = lint_str(src, select=['NBK4', 'NBK5'], memory_config=config)
    assert 'NBK503' in codes(fs)
    assert 'full-mesh units at peak' in fs[0].message
    small = lint.make_config(512, dtype_bytes=8, hbm_bytes=16e9)
    assert lint_str(src, select=['NBK4', 'NBK5'],
                    memory_config=small) == []
