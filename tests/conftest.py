"""Test configuration.

Tests run on CPU with 8 virtual devices so the multi-device code paths
(shard_map collectives, distributed FFT, halo exchange) are exercised
without TPU hardware — the analog of the reference CI running the same
suite under ``mpirun -n 4`` (reference .github/workflows/main.yaml:44-49).

Run with ``JAX_PLATFORMS=cpu`` in the environment (the driver's command
does); the 8 virtual devices and x64 are set here, before any backend
initializes.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import numpy as np  # noqa: F401
import pytest

from nbodykit_tpu._jax_compat import (enable_compile_cache,  # noqa: E402
                                      set_cpu_devices)

set_cpu_devices(8)
jax.config.update("jax_enable_x64", True)
# the suite is compile-dominated; cached re-runs skip nearly all of it
enable_compile_cache()

assert len(jax.devices("cpu")) == 8, \
    "multi-device test setup failed: expected 8 CPU devices"


# ---------------------------------------------------------------------------
# fast/slow tiers. The box running CI has ONE core simulating 8 devices,
# so the suite is wall-clock dominated by shard_map programs. Tests
# measured >= ~2.5 s (see docs/COMPONENTS.md "test tiers") are marked
# slow centrally here; `pytest -m "not slow"` is the fast tier.
_SLOW = {
    "test_convpower.py::test_convpower_periodic_consistency",
    "test_coverage_extras.py::test_fftpower_dk_zero_unique_edges",
    "test_coverage_extras.py::test_paint_sort_method_end_to_end",
    "test_coverage_extras.py::test_readout_device_count_invariance",
    "test_dist_sort.py::test_catalog_sort_multi_device",
    "test_dist_sort.py::test_dist_sort_fast_path_engages",
    "test_dist_sort.py::test_dist_sort_floats",
    "test_dist_sort.py::test_dist_sort_matches_numpy[10001]",
    "test_dist_sort.py::test_dist_sort_matches_numpy[1000]",
    "test_dist_sort.py::test_dist_sort_matches_numpy[4096]",
    "test_dist_sort.py::test_dist_sort_skewed_fallback",
    "test_extras.py::test_demo_halo_catalog_and_populate",
    "test_fftpower.py::test_fftcorr_runs_and_integrates[multi]",
    "test_fftpower.py::test_fftpower_cross[multi]",
    "test_fftpower.py::test_fftpower_shotnoise_flat[multi]",
    "test_fftpower.py::test_fftpower_shotnoise_flat[single]",
    "test_fftpower.py::test_linear_mesh_recovers_power[multi]",
    "test_fof.py::test_fof_com_periodic",
    "test_forward.py::test_forward_served_end_to_end_with_shadow_verify",
    "test_forward.py::test_kdk_gradient_matches_fd_multi",
    "test_forward.py::test_recovery_beats_fftrecon_128",
    "test_forward.py::test_recovery_beats_fftrecon_small",
    "test_fof.py::test_fof_features_and_com",
    "test_fof.py::test_fof_matches_brute_force",
    "test_fof.py::test_fof_mean_separation_units",
    "test_fof.py::test_fof_periodic_wrap",
    "test_fof.py::test_fof_to_halos",
    "test_fof.py::test_fof_two_well_separated_clusters",
    "test_groups.py::test_fibercollisions_isolated",
    "test_groups.py::test_fibercollisions_pair",
    "test_ingest.py::test_cache_fits_predicate_prices_eviction",
    "test_ingest.py::test_cache_hit_bit_identical_and_zero_reads",
    "test_ingest.py::test_cache_misses_when_bytes_change",
    "test_ingest.py::test_eviction_under_shrunken_budget_reingests",
    "test_ingest.py::test_fault_mid_stream_resumes_without_repainting",
    "test_ingest.py::test_host_never_holds_the_catalog",
    "test_ingest.py::test_overlap_and_serial_paths_bit_identical",
    "test_ingest.py::test_resume_refuses_changed_catalog",
    "test_ingest.py::test_streamed_bit_identical_to_whole_load",
    "test_groups.py::test_fibercollisions_triplet_chain",
    "test_io.py::test_mesh_save_and_bigfile_mesh",
    "test_lognormal.py::test_lognormal_columns",
    "test_lognormal.py::test_lognormal_device_count_invariance",
    "test_lognormal.py::test_lognormal_power_recovery",
    "test_lognormal.py::test_unitary_amplitude_reduces_variance",
    "test_mesh_base.py::test_catalog_mesh_selection_column",
    "test_mesh_base.py::test_interlacing_preserves_low_k",
    "test_mesh_base.py::test_mesh_resample_down",
    "test_mesh_base.py::test_value_column_weighting",
    "test_misc_algorithms.py::test_3pcf_brute_force[0]",
    "test_misc_algorithms.py::test_3pcf_brute_force[1]",
    "test_misc_algorithms.py::test_3pcf_brute_force[2]",
    "test_misc_algorithms.py::test_3pcf_nonperiodic_no_double_count",
    "test_misc_algorithms.py::test_fftrecon_reduces_displacement",
    "test_misc_algorithms.py::test_fof_nonperiodic",
    "test_misc_algorithms.py::test_fof_peak_columns",
    "test_misc_algorithms.py::test_hod_populate",
    "test_misc_algorithms.py::test_hod_reproducible",
    "test_paircount.py::test_2pcf_clustered_signal",
    "test_paircount.py::test_2pcf_landy_szalay_matches_natural",
    "test_paircount.py::test_2pcf_natural_uniform_is_zero",
    "test_paircount.py::test_2pcf_projected_wp",
    "test_paircount.py::test_paircount_1d_brute_force",
    "test_paircount.py::test_paircount_2d_mu_bins",
    "test_paircount.py::test_paircount_cross",
    "test_paircount.py::test_paircount_projected",
    "test_paircount.py::test_survey_2pcf_runs",
    "test_paircount.py::test_survey_paircount_angular",
    "test_paircount.py::test_wedges_to_poles",
    "test_pmesh.py::test_dist_irfftn_roundtrip",
    "test_pmesh.py::test_paint_clustered_no_mass_loss",
    "test_pmesh.py::test_paint_device_count_invariance[cic]",
    "test_pmesh.py::test_paint_device_count_invariance[tsc]",
    "test_pmesh.py::test_paint_mass_conservation[multi-cic]",
    "test_pmesh.py::test_paint_mass_conservation[multi-nnb]",
    "test_pmesh.py::test_paint_mass_conservation[multi-pcs]",
    "test_pmesh.py::test_paint_mass_conservation[multi-tsc]",
    "test_pmesh.py::test_paint_nnb_is_histogram[multi]",
    "test_pmesh.py::test_paint_non_divisible_N[multi]",
    "test_pmesh.py::test_paint_non_divisible_N[single]",
    "test_pmesh.py::test_readout_constant_field[multi]",
    "test_pmesh.py::test_readout_constant_field[single]",
    "test_pmesh.py::test_readout_linear_gradient[multi]",
    "test_pmesh.py::test_readout_linear_gradient[single]",
    "test_pmesh.py::test_uniform_particle_grid[multi]",
    "test_pmesh.py::test_uniform_particle_grid[single]",
    "test_pmesh.py::test_whitenoise_unitary",
}


# every paint engine of ops/paint.py with the options that select it:
# what tests/test_paint_kernels.py and tests/test_integrity.py hold to
# the scatter oracle. An engine added to ops/paint.py gets a line here
# (the Pallas deposit is interpreted off the chip: tests/
# test_paint_pallas.py and tests/test_tpu_compile.py hold it).
_FULL = {'paint_chunk_size': 1024 * 1024 * 16, 'mesh_dtype': 'f4'}
PAINT_CANDIDATES = {name: dict(_FULL, **opts) for name, opts in {
    'scatter': {'paint_method': 'scatter'},
    'scatter-chunk4m': {'paint_method': 'scatter',
                        'paint_chunk_size': 1024 * 1024 * 4},
    'sort': {'paint_method': 'sort'},
    'segsum-argsort': {'paint_method': 'segsum',
                       'paint_order': 'argsort'},
    'segsum-radix': {'paint_method': 'segsum', 'paint_order': 'radix'},
    'streams2': {'paint_method': 'streams', 'paint_streams': 2},
    'streams4': {'paint_method': 'streams', 'paint_streams': 4},
    'streams8': {'paint_method': 'streams', 'paint_streams': 8},
    'mxu-argsort-xla': {'paint_method': 'mxu', 'paint_order': 'argsort',
                        'paint_deposit': 'xla'},
    'mxu-radix-xla': {'paint_method': 'mxu', 'paint_order': 'radix',
                      'paint_deposit': 'xla'},
    'scatter-bf16': {'paint_method': 'scatter', 'mesh_dtype': 'bf16'},
    'streams4-bf16': {'paint_method': 'streams', 'paint_streams': 4,
                      'mesh_dtype': 'bf16'},
    'streams8-bf16': {'paint_method': 'streams', 'paint_streams': 8,
                      'mesh_dtype': 'bf16'},
}.items()}


def pytest_collection_modifyitems(config, items):
    for item in items:
        key = "::".join(item.nodeid.split("/")[-1].split("::")[-2:])
        if key in _SLOW:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def no_binning_program_kept():
    """``fftpower._binning_program`` keeps its programs for the process,
    and tests share one: every test starts and leaves with none kept, so
    that one which counts hits, misses or traces, or patches what the
    program's body reads and its key does not (``edge_count_index``,
    ``instrumented_jit``, ``scope``), meets no other test's program and
    leaves none of its own.  (A test that changes such a patch half way
    clears the cache there itself.)"""
    from nbodykit_tpu.algorithms.fftpower import _binning_program
    _binning_program.cache_clear()
    yield
    _binning_program.cache_clear()


@pytest.fixture(scope='session')
def cpu8():
    """An 8-device CPU mesh."""
    from nbodykit_tpu.parallel.runtime import cpu_mesh
    return cpu_mesh()


# Parametrized ambient mesh: single device and the 8-device CPU mesh.
# Mirrors the reference's `@pytest.mark.parametrize("comm", [MPI.COMM_WORLD])`
# + 1-rank/4-rank CI matrix: the same test body must give device-count
# invariant results.
@pytest.fixture(params=['single', 'multi'])
def comm(request):
    from nbodykit_tpu.parallel.runtime import cpu_mesh
    if request.param == 'single':
        return cpu_mesh(1)
    return cpu_mesh()
