"""The judge's own tests in the driver's run (ISSUE 29, ROADMAP D8).

Every verdict in ``PERF_LEDGER.jsonl`` rests on ``perf/``: the harness,
the xplane and scope readers, the drivers, the byte counts the roofline
shares divide by.  Its rehearsals live in ``perf/tests/`` and were
written for their own process (four virtual CPU devices, x64 off:
``perf/tests/test_perf_harness.py:18-20``; this directory's conftest
sets eight and x64 on), so they run here as one child ``pytest`` in
those settings, once, and each case below reports one rehearsal's own
outcome.  The list is a literal so that every xdist worker collects
the same cases and nothing runs at import; a rehearsal added under
``perf/tests/`` fails ``test_every_rehearsal_is_listed`` until it is
named here.
"""

import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REHEARSALS = [
    'test_convpower_cell.py::test_convpower_driver_end_to_end',
    'test_convpower_cell.py::'
    'test_convpower_verify_catches_a_result_that_moved',
    'test_convpower_cell.py::'
    'test_convpower_oracle_catches_a_wrong_answer',
    'test_convpower_cell.py::test_convpower_readers_on_a_survey_call',
    'test_convpower_cell.py::'
    'test_convpower_readers_without_the_survey_scopes',
    'test_convpower_cell.py::'
    'test_convpower_rooflines_withheld_above_the_unscoped_limit',
    'test_convpower_cell.py::test_ylm_bytes_and_nfft_by_hand',
    'test_convpower_cell.py::test_the_new_cell_reports_its_metrics',
    'test_convpower_cell.py::'
    'test_convpower_timed_result_is_held_to_the_reference',
    'test_four_chip_cell.py::test_a2a_bytes',
    'test_four_chip_cell.py::test_readers_with_both_layers[host]',
    'test_four_chip_cell.py::test_readers_with_both_layers[op_name]',
    'test_four_chip_cell.py::test_readers_without_the_layers',
    'test_four_chip_cell.py::test_readers_on_one_device',
    'test_four_chip_cell.py::'
    'test_a2a_ici_share_withheld_above_the_unscoped_limit',
    'test_four_chip_cell.py::test_the_cell_reports_the_new_metrics',
    'test_host_ledger.py::test_gaps_by_the_hosts_innermost_scope',
    'test_host_ledger.py::test_gaps_under_a_root_alone_are_unscoped',
    'test_host_ledger.py::'
    'test_every_host_line_counts_and_roots_do_not',
    'test_host_ledger.py::test_ring_records_are_selected_by_time',
    'test_host_ledger.py::test_retrace_from_the_three_compile_spans',
    'test_host_ledger.py::'
    'test_readers_say_nothing_where_there_is_nothing_to_read',
    'test_host_ledger.py::'
    'test_a_recorded_lab_call_reads_the_same_gaps_both_ways',
    'test_host_ledger.py::'
    'test_load_reads_annotations_and_the_sessions_clock',
    'test_paircount_cell.py::test_paircount_driver_end_to_end',
    'test_paircount_cell.py::test_paircount_verify_catches_what_moved',
    'test_paircount_cell.py::'
    'test_paircount_oracle_refuses_a_float_count',
    'test_paircount_cell.py::'
    'test_plain_reference_two_ways_and_its_bracket',
    'test_paircount_cell.py::test_paircount_readers_on_one_call',
    'test_paircount_cell.py::test_paircount_readers_on_the_parent',
    'test_paircount_cell.py::'
    'test_pair_rate_share_withheld_above_the_unscoped_limit',
    'test_paircount_cell.py::test_pair_flops_by_hand',
    'test_paircount_cell.py::test_the_new_cell_reports_its_metrics',
    'test_perf_harness.py::test_lab_driver_one_device',
    'test_perf_harness.py::test_lab_driver_four_virtual_devices',
    'test_perf_harness.py::test_lab_oracle_catches_a_wrong_answer',
    'test_perf_harness.py::test_served_driver',
    'test_perf_harness.py::test_traced_run_on_the_cpu_reads_the_spans',
    'test_perf_harness.py::test_the_program_refuses_the_cpu',
    'test_perf_harness.py::test_xplane_arithmetic_on_synthetic_events',
    'test_perf_harness.py::test_xplane_self_times_and_labels',
    'test_perf_harness.py::test_span_readers_on_a_recorded_file',
    'test_perf_harness.py::test_work_functions',
    'test_perf_harness.py::test_peaks_table',
    'test_perf_harness.py::'
    'test_lattice_count_against_brute_force[16-0.0]',
    'test_perf_harness.py::'
    'test_lattice_count_against_brute_force[32-0.001]',
    'test_perf_harness.py::'
    'test_lattice_count_against_brute_force[24-0.0]',
    'test_perf_harness.py::test_manifest',
    'test_scopes.py::test_scope_stack_and_layer',
    'test_scopes.py::'
    'test_resolve_gives_what_the_compiler_left_bare_a_scope',
    'test_scopes.py::test_eager_call_joined_through_the_ids',
    'test_scopes.py::test_a_launch_that_cannot_be_found_is_not_guessed',
    'test_scopes.py::test_served_request_by_op_name_alone',
    'test_scopes.py::test_a_program_without_scopes_reads_nothing',
    'test_scopes.py::test_fft_roofline_and_its_guard',
    'test_scopes.py::test_recorded_lab_call_every_launch_comes_home',
    'test_scopes.py::'
    'test_recorded_served_request_bare_scatter_is_paint',
    'test_scopes.py::'
    'test_load_reads_metadata_stats_links_and_the_programs_hlo',
]


@pytest.fixture(scope='module')
def outcomes(tmp_path_factory):
    """``{rehearsal: (outcome, detail)}`` from one run of
    ``perf/tests`` in a process of its own."""
    xml = str(tmp_path_factory.mktemp('perf_rehearsals') / 'junit.xml')
    # the child takes the settings its files ask for, not this
    # process's: their setdefaults decide the device count, and no
    # option of the outer pytest run reaches it
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_ENABLE_X64')
           and not k.startswith(('PYTEST_', 'NBKIT_'))}
    env['JAX_PLATFORMS'] = 'cpu'
    proc = subprocess.run(
        [sys.executable, '-m', 'pytest', 'perf/tests', '-q',
         '-p', 'no:cacheprovider', '-p', 'no:randomly',
         '--junitxml', xml],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    if not os.path.exists(xml):
        raise RuntimeError('perf/tests did not run (exit %d):\n%s\n%s'
                           % (proc.returncode, proc.stdout[-2000:],
                              proc.stderr[-2000:]))
    out = {}
    for case in ET.parse(xml).iter('testcase'):
        name = '%s.py::%s' % (case.get('classname').rsplit('.', 1)[-1],
                              case.get('name'))
        outcome, detail = 'passed', ''
        for child in case:
            if child.tag in ('failure', 'error', 'skipped'):
                outcome = child.tag
                detail = '%s\n%s' % (child.get('message') or '',
                                     child.text or '')
                break
        out[name] = (outcome, detail)
    return out


@pytest.mark.parametrize('rehearsal', REHEARSALS)
def test_rehearsal(rehearsal, outcomes):
    assert rehearsal in outcomes, \
        '%s did not run under perf/tests' % rehearsal
    outcome, detail = outcomes[rehearsal]
    if outcome == 'skipped':
        pytest.skip(detail)
    assert outcome == 'passed', detail


def test_every_rehearsal_is_listed(outcomes):
    assert sorted(outcomes) == sorted(REHEARSALS)
