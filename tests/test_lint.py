"""dklint self-tests: fixture firing, suppressions, baseline, and the
package-wide gate (distkeras_tpu/ must be clean modulo the committed
baseline).  Pure AST work — no jax import, no devices."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.lint

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO_ROOT, "tests", "lint_fixtures")
BASELINE = os.path.join(REPO_ROOT, "tools", "dklint", "baseline.json")
SELFLINT_BASELINE = os.path.join(
    REPO_ROOT, "tools", "dklint", "selflint_baseline.json"
)

sys.path.insert(0, REPO_ROOT)

from tools.dklint import analyze, apply_baseline, load_baseline  # noqa: E402
from tools.dklint.registry import all_rules  # noqa: E402


def _run(fixture, select):
    path = os.path.join(FIXTURES, fixture)
    findings, files = analyze([path], root=REPO_ROOT, select=select)
    return [(f.rule, f.line) for f in findings], files


# --------------------------------------------------------------- per-rule

def test_dk101_host_sync_fixture():
    got, _ = _run("dk101_host_sync.py", ["DK101"])
    assert got == [
        ("DK101", 16),  # .item() in jitted fn
        ("DK101", 17),  # np.asarray in jitted fn
        ("DK101", 18),  # float() on traced arg
        ("DK101", 19),  # jax.device_get
        ("DK101", 25),  # block_until_ready in scan body
        ("DK101", 37),  # .item() in engine hot method
        ("DK101", 52),  # float() on x = x * 2.0 — still param-derived
    ]


def test_dk101_suppression_and_cold_paths():
    got, _ = _run("dk101_host_sync.py", ["DK101"])
    lines = [ln for _, ln in got]
    assert 20 not in lines  # trailing `# dklint: disable=DK101`
    assert 36 not in lines  # float() on a local int, not a traced arg
    assert 40 not in lines  # np.asarray outside any hot path


def test_dk101_v3_provenance_kills_reassignment_fps():
    """The v2 false-positive class: a parameter rebound to a host constant
    (``x = 0.0; float(x)``) and a closure constant synced inside a jitted
    factory product are trace-time constants, not per-step syncs."""
    got, _ = _run("dk101_host_sync.py", ["DK101"])
    lines = [ln for _, ln in got]
    assert 46 not in lines  # float(x) after x = 0.0 rebind
    assert 60 not in lines  # const.item() on an enclosing-factory constant


def test_dk102_recompile_fixture():
    got, _ = _run("dk102_recompile.py", ["DK102"])
    assert got == [
        ("DK102", 8),   # jax.jit(...)(...) immediate invocation
        ("DK102", 18),  # jit construction inside a for loop
        ("DK102", 25),  # traced arg as branch condition
        ("DK102", 34),  # traced arg as range() bound
    ]


def test_dk102_suppression_and_statics():
    got, _ = _run("dk102_recompile.py", ["DK102"])
    lines = [ln for _, ln in got]
    assert 12 not in lines  # suppressed immediate invocation
    assert 27 not in lines  # literal range bound
    assert 52 not in lines  # static_argnums-covered range bound


def test_dk103_donation_fixture():
    got, _ = _run("dk103_donation.py", ["DK103"])
    assert got == [
        ("DK103", 9),   # state.loss read after donating call
        ("DK103", 21),  # read after immediate donate-invocation
    ]


def test_dk103_rebind_and_suppression():
    got, _ = _run("dk103_donation.py", ["DK103"])
    lines = [ln for _, ln in got]
    assert 15 not in lines  # rebound on the call line
    assert 16 not in lines  # use after rebind is the blessed idiom
    assert 27 not in lines  # suppressed


def test_dk104_mesh_axes_fixture():
    got, _ = _run("dk104_mesh_axes.py", ["DK104"])
    assert got == [
        ("DK104", 20),  # psum over typo'd axis
        ("DK104", 21),  # all_gather over unknown axis
        ("DK104", 22),  # axis_index over unknown axis
    ]


def test_dk104_declared_axes_and_suppression():
    got, _ = _run("dk104_mesh_axes.py", ["DK104"])
    lines = [ln for _, ln in got]
    assert 14 not in lines  # *_AXIS constant counts as declared
    assert 15 not in lines  # Mesh(..., ("workers", "seq")) literal counts
    assert 27 not in lines  # suppressed


def test_dk105_locks_fixture():
    got, _ = _run("dk105_locks.py", ["DK105"])
    assert got == [
        ("DK105", 14),  # guarded attr written off-lock
        ("DK105", 22),  # guarded list mutated off-lock
    ]


def test_dk105_exemptions_and_suppression():
    got, _ = _run("dk105_locks.py", ["DK105"])
    lines = [ln for _, ln in got]
    assert 10 not in lines  # __init__ writes exempt
    assert 17 not in lines  # suppressed
    assert 31 not in lines  # attr never touched under the lock
    assert 39 not in lines  # class owns no lock


def test_dk106_wallclock_fixture():
    got, _ = _run("dk106_wallclock.py", ["DK106"])
    assert got == [
        ("DK106", 7),   # deadline = time.time() + timeout
        ("DK106", 8),   # while time.time() < deadline
        ("DK106", 15),  # time.time() - t0
        ("DK106", 19),  # flagged through max(0.0, ...) nesting
    ]


def test_dk106_timestamps_and_suppression():
    got, _ = _run("dk106_wallclock.py", ["DK106"])
    lines = [ln for _, ln in got]
    assert 13 not in lines  # bare t0 = time.time() assignment
    assert 23 not in lines  # suppressed deadline
    assert 29 not in lines  # bare timestamp assignment
    assert 30 not in lines  # timestamp in a dict literal
    assert 36 not in lines  # perf_counter duration is the blessed idiom


def test_dk107_finiteness_fixture():
    got, _ = _run("dk107_finiteness.py", ["DK107"])
    assert got == [
        ("DK107", 11),  # bool(jnp.isnan(...)) in loop body
        ("DK107", 13),  # .item() on a finiteness check per step
        ("DK107", 14),  # np.asarray hostification
        ("DK107", 15),  # jax.device_get hostification
        ("DK107", 20),  # while-test through .any()
        ("DK107", 28),  # if-test through jnp.any reduction
        ("DK107", 35),  # assert syncing every step
    ]


def test_dk107_in_graph_and_suppression():
    got, _ = _run("dk107_finiteness.py", ["DK107"])
    lines = [ln for _, ln in got]
    assert 41 not in lines  # suppressed
    assert 46 not in lines  # jnp.where masking stays on device
    assert 47 not in lines  # summed non-finite counter stays on device
    assert 53 not in lines  # one-off host check outside any loop


def test_dk108_collectives_fixture():
    got, _ = _run("dk108_collectives.py", ["DK108"])
    assert got == [
        ("DK108", 19),  # psum over an axis the shard_map mesh never binds
        ("DK108", 27),  # pmean over 'batch' under pmap(axis_name="devices")
        ("DK108", 69),  # lax.cond branches with different collectives
    ]


def test_dk108_bound_axes_and_suppression():
    got, _ = _run("dk108_collectives.py", ["DK108"])
    lines = [ln for _, ln in got]
    assert 16 not in lines  # axis bound by the shard_map mesh
    assert 35 not in lines  # axis via *_AXIS constant matches vmap axis_name
    assert 42 not in lines  # nested vmap: outer shard_map axes still bound
    assert 53 not in lines  # suppressed
    assert 85 not in lines  # cond with identical collectives per branch


def test_dk109_traced_branch_fixture():
    got, _ = _run("dk109_traced_branch.py", ["DK109"])
    assert got == [
        ("DK109", 8),   # if on traced param of jit-by-name fn
        ("DK109", 14),  # while on traced param 'x'
        ("DK109", 14),  # ... and on traced param 'lo'
        ("DK109", 64),  # if on y = x * 2 — still param-derived
    ]


def test_dk109_exemptions_and_suppression():
    got, _ = _run("dk109_traced_branch.py", ["DK109"])
    lines = [ln for _, ln in got]
    assert 20 not in lines  # `x is None` structure dispatch
    assert 22 not in lines  # .shape comparison is trace-time static
    assert 24 not in lines  # isinstance
    assert 30 not in lines  # static_argnums at the jit call site
    assert 36 not in lines  # suppressed
    assert 43 not in lines  # @jax.jit-decorated fn is DK102's territory
    assert 57 not in lines  # v3: branch on x after x = 0 rebind is host flow


def _run_dk110(tmp_path):
    """DK110 only fires inside the ``distkeras_tpu`` package, so the fixture
    is analyzed from a synthetic package root rather than the checkout."""
    src = open(os.path.join(FIXTURES, "dk110_print_logging.py")).read()
    pkg = tmp_path / "distkeras_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "hot.py").write_text(src)
    findings, _ = analyze([str(pkg / "hot.py")], root=str(tmp_path),
                          select=["DK110"])
    return [(f.rule, f.line) for f in findings]


def test_dk110_print_logging_fixture(tmp_path):
    assert _run_dk110(tmp_path) == [
        ("DK110", 14),  # print() in a hot module
        ("DK110", 15),  # logging.getLogger(__name__)
        ("DK110", 16),  # from-imported getLogger alias
    ]


def test_dk110_exemptions_and_suppression(tmp_path):
    lines = [ln for _, ln in _run_dk110(tmp_path)]
    assert 22 not in lines  # `emit = print` reference, not a call
    assert 23 not in lines  # suppressed
    assert 28 not in lines  # __main__ guard block is a script entry point


def test_dk110_out_of_package_is_silent():
    # the same source analyzed as tests.lint_fixtures.* is out of scope —
    # tools/ and tests/ keep their CLIs and fixtures
    got, _ = _run("dk110_print_logging.py", ["DK110"])
    assert got == []


def _run_in_package(tmp_path, fixture, select, golden=None):
    """Package-scoped rules (DK111/DK113/DK114) are exercised from a
    synthetic ``distkeras_tpu`` package root, like ``_run_dk110``.  When
    ``golden`` is given it is written to tests/golden/fixture_metrics.txt
    under the same root so DK114 sees it as the exported ground truth."""
    src = open(os.path.join(FIXTURES, fixture)).read()
    pkg = tmp_path / "distkeras_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(src)
    if golden is not None:
        gd = tmp_path / "tests" / "golden"
        gd.mkdir(parents=True)
        (gd / "fixture_metrics.txt").write_text(golden)
    findings, _ = analyze([str(pkg / "mod.py")], root=str(tmp_path),
                          select=select)
    return [(f.rule, f.line) for f in findings]


def test_dk111_prng_lineage_fixture(tmp_path):
    assert _run_in_package(tmp_path, "dk111_prng_lineage.py", ["DK111"]) == [
        ("DK111", 15),  # second split of the same key (the sampling.py bug)
        ("DK111", 21),  # split then a draw from the already-consumed parent
        ("DK111", 28),  # key consumed in a loop but never advanced there
    ]


def test_dk111_clean_lineages_are_silent(tmp_path):
    lines = [ln for _, ln in
             _run_in_package(tmp_path, "dk111_prng_lineage.py", ["DK111"])]
    assert 35 not in lines and 36 not in lines  # key rebound between draws
    assert 42 not in lines and 43 not in lines  # exclusive if/else arms
    assert 49 not in lines and 50 not in lines  # fold_in + one split coexist
    assert 57 not in lines and 58 not in lines  # key advanced per iteration
    assert 63 not in lines  # vmapped split: not a Name-keyed consumption
    assert 69 not in lines  # inline PRNGKey construction consumed once


def test_dk111_out_of_package_is_silent():
    got, _ = _run("dk111_prng_lineage.py", ["DK111"])
    assert got == []


def test_dk112_blocking_fixture():
    got, _ = _run("dk112_blocking.py", ["DK112"])
    assert got == [
        ("DK112", 17),  # time.sleep in a jitted step
        ("DK112", 22),  # sock.recv in a helper reachable from the jit
        ("DK112", 38),  # untimed queue.get() in the engine decode loop
        ("DK112", 39),  # untimed lock.acquire() in the decode loop
        ("DK112", 43),  # open() in a method the decode loop calls
    ]


def test_dk112_cold_and_timed_calls_are_silent():
    got, _ = _run("dk112_blocking.py", ["DK112"])
    lines = [ln for _, ln in got]
    assert 48 not in lines and 49 not in lines  # cold function: clean
    assert 59 not in lines  # cv.wait(timeout=...) is bounded
    assert 60 not in lines  # queue.get(timeout=...) is bounded
    assert 61 not in lines  # lock.acquire(timeout=...) is bounded
    assert 64 not in lines  # dict.get(key) is not queue.get()


def test_dk112_prefetch_ring_fixture():
    got, _ = _run("dk112_datapipe.py", ["DK112"])
    assert got == [
        ("DK112", 43),  # .item() in the gather path (ring-hot only)
        ("DK112", 44),  # .tolist() in the gather path (ring-hot only)
        ("DK112", 45),  # time.sleep throttling the producer
    ]


def test_dk112_ring_queue_waits_are_silent():
    got, _ = _run("dk112_datapipe.py", ["DK112"])
    lines = [ln for _, ln in got]
    assert 26 not in lines  # q.put(timeout=_TICK) bounded offer
    assert 57 not in lines  # q.get(timeout=_TICK) bounded pull
    assert 66 not in lines  # .item() outside the ring closure: clean


def test_dk112_package_ring_is_clean():
    """The shipped PrefetchRing must satisfy its own rule: bounded waits
    everywhere, no host sync in the producer."""
    path = os.path.join(REPO_ROOT, "distkeras_tpu", "datapipe", "ring.py")
    findings, _ = analyze([path], root=REPO_ROOT, select=["DK112"])
    assert [(f.rule, f.line) for f in findings] == []


def test_dk113_daemon_protocol_fixture(tmp_path):
    assert _run_in_package(
        tmp_path, "dk113_daemon_protocol.py", ["DK113"]
    ) == [
        ("DK113", 20),  # verb 'submit': double reply on one path
        ("DK113", 20),  # dispatch chain has no else leg
        ("DK113", 24),  # verb 'status': replies on some paths only
        ("DK113", 28),  # verb 'drop': never replies
        ("DK113", 34),  # send_data while holding self._cv
        ("DK113", 64),  # endpoint falls off the end
        ("DK113", 70),  # bare return in an endpoint handler
    ]


def test_dk113_disciplined_server_is_silent(tmp_path):
    lines = [ln for _, ln in _run_in_package(
        tmp_path, "dk113_daemon_protocol.py", ["DK113"])]
    # DisciplinedServer (single reply per verb, send after releasing the cv,
    # raise path exempt, else leg present) spans lines 38-60; the
    # disciplined try/except endpoint spans 73-78 — all silent
    assert not any(38 <= ln <= 60 for ln in lines)
    assert not any(73 <= ln <= 78 for ln in lines)


_DK114_GOLDEN = (
    "# HELP serving_widget_latency_seconds latency\n"
    "# TYPE serving_widget_latency_seconds histogram\n"
    "# HELP serving_widgets_total widgets\n"
    "# TYPE serving_widgets_total counter\n"
)


def test_dk114_metric_hygiene_fixture(tmp_path):
    assert _run_in_package(
        tmp_path, "dk114_metric_hygiene.py", ["DK114"], golden=_DK114_GOLDEN
    ) == [
        ("DK114", 16),  # near-miss of golden serving_widgets_total
        ("DK114", 18),  # gauge vs the golden histogram kind
        ("DK114", 25),  # later-site kind conflict with the line-20 gauge
    ]


def test_dk114_clean_registrations_are_silent(tmp_path):
    lines = [ln for _, ln in _run_in_package(
        tmp_path, "dk114_metric_hygiene.py", ["DK114"],
        golden=_DK114_GOLDEN)]
    assert 27 not in lines and 28 not in lines  # idempotent re-registration
    assert 31 not in lines  # exact golden match is ground truth, not a typo
    assert 33 not in lines  # short names never near-miss


def test_dk114_label_disagreement_across_goldens(tmp_path):
    src = (
        "def register(registry):\n"
        '    registry.counter("fixture_rpc_calls_total", help="rpcs")\n'
    )
    pkg = tmp_path / "distkeras_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "mod.py").write_text(src)
    gd = tmp_path / "tests" / "golden"
    gd.mkdir(parents=True)
    (gd / "a_metrics.txt").write_text(
        "# TYPE fixture_rpc_calls_total counter\n"
        'fixture_rpc_calls_total{run_id="x"} 1\n'
    )
    (gd / "b_metrics.txt").write_text(
        "# TYPE fixture_rpc_calls_total counter\n"
        'fixture_rpc_calls_total{run_id="x",verb="submit"} 1\n'
    )
    findings, _ = analyze([str(pkg / "mod.py")], root=str(tmp_path),
                          select=["DK114"])
    assert len(findings) == 1
    assert "disagree on label keys" in findings[0].message


def test_dk115_socket_timeout_fixture():
    got, _ = _run("dk115_server.py", ["DK115"])
    assert got == [
        ("DK115", 10),  # timeout-less create_connection (call site)
        ("DK115", 30),  # recv on a parameter socket, no settimeout on path
        ("DK115", 34),  # accept on a parameter listener
        ("DK115", 35),  # recv on the accept-derived conn (inherits nothing)
    ]


def test_dk116_retry_cap_fixture():
    got, _ = _run("dk116_retry_daemon.py", ["DK116"])
    assert got == [
        ("DK116", 11),  # hot reconnect: swallowed OSError, no pacing
        ("DK116", 20),  # networking helpers retried forever, unpaced
    ]


def test_dk116_out_of_scope_module_is_silent(tmp_path):
    """The same unbounded retry outside the daemon/server/tier scope stays
    unflagged — a one-shot script may poll however it likes."""
    src = (
        "import socket\n"
        "def f(host):\n"
        "    while True:\n"
        "        try:\n"
        "            return socket.create_connection((host, 1), timeout=1)\n"
        "        except OSError:\n"
        "            pass\n"
    )
    mod = tmp_path / "batch_tool.py"
    mod.write_text(src)
    findings, _ = analyze([str(mod)], root=str(tmp_path), select=["DK116"])
    assert findings == []


def test_dk117_cardinality_fixture(tmp_path):
    assert _run_in_package(
        tmp_path, "dk117_cardinality.py", ["DK117"]
    ) == [
        ("DK117", 11),  # f-string metric name interpolating request_id
        ("DK117", 14),  # % composition with a trace_id variable
        ("DK117", 16),  # .format() with a job_id attribute
        ("DK117", 18),  # labels= dict with a request_id key
        ("DK117", 20),  # labels= dict value reading trace_id
        ("DK117", 22),  # labels= expression reading request_id
    ]


def test_dk117_sanctioned_homes_are_silent(tmp_path):
    """Literal names, bounded-enum families, run_id labels, and trace-span
    args (the sanctioned home for request ids) all stay unflagged."""
    lines = [ln for _, ln in _run_in_package(
        tmp_path, "dk117_cardinality.py", ["DK117"])]
    assert all(ln < 26 for ln in lines), lines  # everything in clean() silent


def test_dk117_out_of_package_is_silent():
    got, _ = _run("dk117_cardinality.py", ["DK117"])
    assert got == []


def test_dk117_tenant_labels_fixture(tmp_path):
    assert _run_in_package(
        tmp_path, "dk117_tenant_labels.py", ["DK117"]
    ) == [
        ("DK117", 17),  # f-string metric name interpolating tenant
        ("DK117", 20),  # % composition with a tenant_id variable
        ("DK117", 22),  # labels= dict with a tenant key
        ("DK117", 24),  # labels= dict value reading tenant_id
        ("DK117", 26),  # labels= expression reading tenant
    ]


def test_dk117_tenant_sanctioned_homes_are_silent(tmp_path):
    """Literal names, bounded deploy labels, span args, and the ledger API
    (the sanctioned aggregation home for tenants) all stay unflagged."""
    lines = [ln for _, ln in _run_in_package(
        tmp_path, "dk117_tenant_labels.py", ["DK117"])]
    assert all(ln < 34 for ln in lines), lines  # everything in clean() silent


def test_dk117_accounting_module_is_tenant_exempt(tmp_path):
    """The bounded top-K ledger module itself may carry tenant state — the
    same source analyzed as distkeras_tpu.telemetry.accounting is clean."""
    src = open(os.path.join(FIXTURES, "dk117_tenant_labels.py")).read()
    pkg = tmp_path / "distkeras_tpu"
    sub = pkg / "telemetry"
    sub.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (sub / "__init__.py").write_text("")
    (sub / "accounting.py").write_text(src)
    findings, _ = analyze([str(sub / "accounting.py")], root=str(tmp_path),
                          select=["DK117"])
    assert findings == []


def test_dk118_atomic_publish_fixture():
    got, _ = _run("dk118_checkpoint_pub.py", ["DK118"])
    assert got == [
        ("DK118", 12),  # json.dump into a bare open(path, "w")
        ("DK118", 17),  # fh = open(...); fh.write(...) with no replace
        ("DK118", 23),  # pickle.dump into open(path, "wb")
        ("DK118", 28),  # open(path, "w").write(...) inline
    ]


def test_dk118_clean_idioms_are_silent():
    """tmp + os.replace / os.rename, read mode, append logs, never-written
    handles, non-literal modes, and the suppression comment all stay
    silent — only in-place publication fires."""
    got, _ = _run("dk118_checkpoint_pub.py", ["DK118"])
    lines = [ln for _, ln in got]
    assert all(ln < 31 for ln in lines), lines


def test_dk118_out_of_scope_module_is_silent(tmp_path):
    """The same bare write outside checkpoint/telemetry/discovery scope is
    fine — private scratch files may be written in place."""
    src = (
        "import json\n"
        "def f(path, obj):\n"
        "    with open(path, 'w') as fh:\n"
        "        json.dump(obj, fh)\n"
    )
    mod = tmp_path / "batch_tool.py"
    mod.write_text(src)
    findings, _ = analyze([str(mod)], root=str(tmp_path), select=["DK118"])
    assert findings == []


def test_dk119_shared_state_race_fixture():
    got, _ = _run("dk119_races.py", ["DK119"])
    assert got == [
        ("DK119", 16),  # unlocked write on the spawned root
        ("DK119", 42),  # unguarded read vs a locked writer
        ("DK119", 52),  # unlocked write on a module global
    ]


def test_dk120_lock_order_fixture():
    got, _ = _run("dk120_lock_order.py", ["DK120"])
    assert got == [
        ("DK120", 12),  # a -> b leg of the direct cycle
        ("DK120", 18),  # b -> a leg of the direct cycle
        ("DK120", 24),  # c -> d through the callee
        ("DK120", 34),  # d -> c closing the interprocedural cycle
    ]


def test_dk121_thread_lifecycle_fixture():
    got, _ = _run("dk121_lifecycle.py", ["DK121"])
    assert got == [
        ("DK121", 7),   # non-daemon thread never joined
        ("DK121", 13),  # runner loop without exception containment
    ]


def test_dk121_joined_and_daemon_threads_are_silent():
    got, _ = _run("dk121_lifecycle.py", ["DK121"])
    lines = [ln for _, ln in got]
    assert 22 not in lines  # joined non-daemon thread
    assert 28 not in lines  # daemon thread
    assert 33 not in lines  # contained runner loop


def test_dk122_unit_hygiene_fixture(tmp_path):
    assert _run_in_package(tmp_path, "dk122_units.py", ["DK122"]) == [
        ("DK122", 18),  # counter without _total
        ("DK122", 19),  # seconds tally is still a counter: needs _total
        ("DK122", 21),  # duration histogram in milliseconds (_ms)
        ("DK122", 22),  # latency token, no unit suffix
        ("DK122", 23),  # _time is not a unit
        ("DK122", 25),  # byte gauge without _bytes
    ]


def test_dk122_canonical_names_are_silent(tmp_path):
    lines = [ln for _, ln in _run_in_package(
        tmp_path, "dk122_units.py", ["DK122"])]
    # register_clean spans lines 29-41: canonical suffixes, unitless gauge,
    # a non-duration histogram, and a computed family are all clean
    assert not any(29 <= ln <= 41 for ln in lines)


def test_dk122_out_of_package_is_silent():
    """Same registrations outside the distkeras_tpu package stay unflagged
    — naming conventions only bind the shipped instrument set."""
    got, _ = _run("dk122_units.py", ["DK122"])
    assert got == []


def test_fixed_modules_stay_concurrency_clean():
    """Regression pins for the in-tree fixes: modules whose DK119/DK120/
    DK121 findings were *fixed* (not baselined) must stay clean when
    analyzed alone, with no baseline applied.  (tier.py and engine.py keep
    justified Event-handoff / internally-locked-queue entries in the main
    baseline and are pinned by the package gate instead.)"""
    for mod in ("distkeras_tpu/fleet.py",
                "distkeras_tpu/telemetry/metrics.py",
                "distkeras_tpu/job_deployment.py"):
        findings, _ = analyze([os.path.join(REPO_ROOT, mod)], root=REPO_ROOT,
                              select=["DK119", "DK120", "DK121"])
        assert findings == [], mod + ":\n" + "\n".join(
            f.render() for f in findings)


def test_concurrency_no_false_positive_corpus():
    """The pinned clean corpus: cv-wait (both sides hold the condition),
    lockwatch maybe_wrap/guard_map state, Event handoff with locked
    accesses, and a handler thread with locked registry access must all
    stay finding-free under every concurrency rule."""
    got, _ = _run("dk119_no_fp.py", ["DK119", "DK120", "DK121"])
    assert got == []


def test_dk115_out_of_scope_module_is_silent(tmp_path):
    """Same code outside the daemon/server scope stays unflagged — batch
    code may legitimately block forever."""
    src = "def f(sock):\n    return sock.recv(16)\n"
    mod = tmp_path / "batch_tool.py"
    mod.write_text(src)
    findings, _ = analyze([str(mod)], root=str(tmp_path), select=["DK115"])
    assert findings == []


# ------------------------------------------------------ interprocedural v2

def test_cross_module_host_sync_found_by_v2():
    """The helper's np.asarray is invisible per-module (v1) but hot once the
    jitted caller in the other file is analyzed alongside it."""
    pair = [os.path.join(FIXTURES, "xmod_engine.py"),
            os.path.join(FIXTURES, "xmod_helper.py")]
    findings, _ = analyze(pair, root=REPO_ROOT, select=["DK101"])
    assert [(f.rule, os.path.basename(f.path), f.line) for f in findings] == [
        ("DK101", "xmod_helper.py", 11),
    ]


def test_cross_module_helper_alone_is_cold():
    findings, _ = analyze(
        [os.path.join(FIXTURES, "xmod_helper.py")],
        root=REPO_ROOT, select=["DK101"],
    )
    assert findings == []


# ------------------------------------------------------------ machinery

def test_file_wide_suppression(tmp_path):
    src = (
        "# dklint: disable=DK102\n"
        "import jax\n"
        "def f(x):\n"
        "    return jax.jit(lambda v: v)(x)\n"
    )
    p = tmp_path / "mod.py"
    p.write_text(src)
    findings, _ = analyze([str(p)], root=str(tmp_path), select=["DK102"])
    assert findings == []


def test_disable_all(tmp_path):
    src = (
        "import jax\n"
        "def f(x):\n"
        "    return jax.jit(lambda v: v)(x)  # dklint: disable=all\n"
    )
    p = tmp_path / "mod.py"
    p.write_text(src)
    findings, _ = analyze([str(p)], root=str(tmp_path), select=["DK102"])
    assert findings == []


def test_decorator_line_suppression_covers_the_def(tmp_path):
    """A trailing directive on a decorator line suppresses findings anywhere
    in the decorated function — previously it only covered the decorator's
    own line, which can never carry the finding."""
    src = (
        "import jax\n"
        "@jax.jit  # dklint: disable=DK101\n"
        "def f(x):\n"
        "    return x.item()\n"
    )
    p = tmp_path / "mod.py"
    p.write_text(src)
    findings, _ = analyze([str(p)], root=str(tmp_path), select=["DK101"])
    assert findings == []


def test_decorator_line_suppression_is_scoped(tmp_path):
    """The decorator-line directive covers only its own function."""
    src = (
        "import jax\n"
        "@jax.jit  # dklint: disable=DK101\n"
        "def f(x):\n"
        "    return x.item()\n"
        "@jax.jit\n"
        "def g(x):\n"
        "    return x.item()\n"
    )
    p = tmp_path / "mod.py"
    p.write_text(src)
    findings, _ = analyze([str(p)], root=str(tmp_path), select=["DK101"])
    assert [(f.rule, f.line) for f in findings] == [("DK101", 7)]


def test_multi_rule_disable(tmp_path):
    src = (
        "import jax\n"
        "@jax.jit  # dklint: disable=DK101,DK102\n"
        "def f(x, n):\n"
        "    if n > 0:\n"
        "        return x.item()\n"
        "    return x\n"
    )
    p = tmp_path / "mod.py"
    p.write_text(src)
    findings, _ = analyze(
        [str(p)], root=str(tmp_path), select=["DK101", "DK102"]
    )
    assert findings == []


def test_baseline_cancels_and_reports_stale(tmp_path):
    src = "import jax\ndef f(x):\n    return jax.jit(lambda v: v)(x)\n"
    p = tmp_path / "mod.py"
    p.write_text(src)
    findings, files = analyze([str(p)], root=str(tmp_path), select=["DK102"])
    assert len(findings) == 1
    entry = {"path": "mod.py", "rule": "DK102",
             "text": "return jax.jit(lambda v: v)(x)", "reason": "test"}
    stale_entry = {"path": "mod.py", "rule": "DK102",
                   "text": "this line no longer exists", "reason": "gone"}
    new, stale = apply_baseline(findings, [entry, stale_entry], files)
    assert new == []
    assert stale == [stale_entry]


def test_all_rules_registered():
    assert sorted(all_rules()) == [
        "DK101", "DK102", "DK103", "DK104", "DK105", "DK106", "DK107",
        "DK108", "DK109", "DK110", "DK111", "DK112", "DK113", "DK114",
        "DK115", "DK116", "DK117", "DK118", "DK119", "DK120", "DK121",
        "DK122", "DK123", "DK124", "DK125", "DK126",
    ]


def test_baseline_entries_have_reasons():
    for path in (BASELINE, SELFLINT_BASELINE):
        entries = load_baseline(path)
        assert entries, f"{path} should not be empty-yet-present"
        for e in entries:
            assert e.get("reason", "").strip(), f"baseline entry lacks a reason: {e}"


# ---------------------------------------------------------------- the gate

def test_package_is_clean_modulo_baseline():
    """The enforced invariant: dklint over distkeras_tpu/ yields zero
    findings that the committed baseline does not account for."""
    pkg = os.path.join(REPO_ROOT, "distkeras_tpu")
    findings, files = analyze([pkg], root=REPO_ROOT)
    new, _stale = apply_baseline(findings, load_baseline(BASELINE), files)
    assert new == [], "new dklint findings:\n" + "\n".join(
        f.render() for f in new
    )


def test_tools_and_tests_clean_modulo_selflint_baseline():
    """The self-lint gate: dklint over its own sources and the test tree
    yields nothing the selflint baseline (deliberate fixture violations)
    does not account for."""
    findings, files = analyze(
        [os.path.join(REPO_ROOT, "tools"), os.path.join(REPO_ROOT, "tests")],
        root=REPO_ROOT,
    )
    new, _stale = apply_baseline(
        findings, load_baseline(SELFLINT_BASELINE), files
    )
    assert new == [], "new self-lint findings:\n" + "\n".join(
        f.render() for f in new
    )


def test_cli_exit_codes():
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    ok = subprocess.run(
        [sys.executable, "-m", "tools.dklint", "distkeras_tpu",
         "--root", REPO_ROOT],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    dirty = subprocess.run(
        [sys.executable, "-m", "tools.dklint",
         os.path.join("tests", "lint_fixtures"), "--no-baseline",
         "--root", REPO_ROOT],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert dirty.returncode == 1
    assert "DK101" in dirty.stdout


def test_cli_prune_baseline_roundtrip(tmp_path):
    """--prune-baseline drops entries matching nothing and keeps (with
    reasons) the ones still earning their grandfathering."""
    src = "import jax\ndef f(x):\n    return jax.jit(lambda v: v)(x)\n"
    (tmp_path / "mod.py").write_text(src)
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({
        "version": 1,
        "findings": [
            {"path": "mod.py", "rule": "DK102",
             "text": "return jax.jit(lambda v: v)(x)", "reason": "live"},
            {"path": "mod.py", "rule": "DK102",
             "text": "this line is long gone", "reason": "stale"},
        ],
    }))
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    pruned = subprocess.run(
        [sys.executable, "-m", "tools.dklint", "mod.py",
         "--root", str(tmp_path), "--baseline", str(baseline),
         "--prune-baseline"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
    )
    assert pruned.returncode == 0, pruned.stdout + pruned.stderr
    assert "pruned 1 stale entry, kept 1" in pruned.stdout
    doc = json.loads(baseline.read_text())
    assert [e["reason"] for e in doc["findings"]] == ["live"]
    # round-trip: the pruned baseline still cancels the live finding
    clean = subprocess.run(
        [sys.executable, "-m", "tools.dklint", "mod.py",
         "--root", str(tmp_path), "--baseline", str(baseline)],
        cwd=tmp_path, env=env, capture_output=True, text=True,
    )
    assert clean.returncode == 0, clean.stdout + clean.stderr
    # pruning again is a no-op
    again = subprocess.run(
        [sys.executable, "-m", "tools.dklint", "mod.py",
         "--root", str(tmp_path), "--baseline", str(baseline),
         "--prune-baseline"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
    )
    assert "pruned 0 stale entries, kept 1" in again.stdout


def test_cli_github_format():
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "tools.dklint",
         os.path.join("tests", "lint_fixtures", "dk104_mesh_axes.py"),
         "--no-baseline", "--root", REPO_ROOT, "--format", "github"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert out.returncode == 1
    lines = [ln for ln in out.stdout.splitlines() if ln]
    assert len(lines) == 3
    for ln in lines:
        assert ln.startswith("::warning file=tests/lint_fixtures/dk104_mesh_axes.py,line=")
        assert "title=dklint DK104::" in ln


def test_cli_json_format():
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "tools.dklint",
         os.path.join("tests", "lint_fixtures", "dk104_mesh_axes.py"),
         "--no-baseline", "--root", REPO_ROOT, "--format", "json"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    payload = json.loads(out.stdout)
    assert [f["rule"] for f in payload] == ["DK104"] * 3


def test_cli_sarif_format_roundtrip():
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "tools.dklint",
         os.path.join("tests", "lint_fixtures", "dk104_mesh_axes.py"),
         "--no-baseline", "--root", REPO_ROOT, "--format", "sarif"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert out.returncode == 1
    doc = json.loads(out.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "dklint"
    # the schema requires informationUri, when present, to be an absolute
    # URI — a repo-relative path breaks strict consumers
    info = run["tool"]["driver"].get("informationUri")
    assert info is None or "://" in info
    # every registered rule is described even though only DK104 fired
    rule_ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
    assert rule_ids == sorted(all_rules())
    results = run["results"]
    assert [r["ruleId"] for r in results] == ["DK104"] * 3
    for r in results:
        loc = r["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == \
            "tests/lint_fixtures/dk104_mesh_axes.py"
        assert loc["region"]["startLine"] > 0
        assert loc["region"]["startColumn"] > 0  # SARIF columns are 1-based
        assert r["message"]["text"]


def _git(cwd, *args):
    return subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=cwd, capture_output=True, text=True, check=True,
    )


def test_cli_since_filters_to_changed_files(tmp_path):
    """--since reports only findings in files changed vs. the ref, while
    still analyzing the whole tree (so cross-module facts stay correct)."""
    _git(tmp_path, "init", "-q")
    old = tmp_path / "old.py"
    old.write_text(
        "import jax\ndef f(x):\n    return jax.jit(lambda v: v)(x)\n"
    )
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    new = tmp_path / "new.py"
    new.write_text(
        "import jax\ndef g(x):\n    return jax.jit(lambda v: v)(x)\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "tools.dklint", ".", "--no-baseline",
         "--root", str(tmp_path), "--since", "HEAD", "--format", "json"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
    )
    assert out.returncode == 1, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    # old.py's finding pre-dates the ref and is filtered; untracked new.py
    # counts as changed
    assert [(f["path"], f["rule"]) for f in payload] == [("new.py", "DK102")]
    # with everything committed, the diff set is empty -> clean exit
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "more")
    clean = subprocess.run(
        [sys.executable, "-m", "tools.dklint", ".", "--no-baseline",
         "--root", str(tmp_path), "--since", "HEAD"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
    )
    assert clean.returncode == 0, clean.stdout + clean.stderr


def test_cli_since_with_root_below_git_toplevel(tmp_path):
    """`git diff` paths are cwd-relative (--relative), so a --root that is
    a subdirectory of the git toplevel still matches root-relative
    findings instead of silently filtering everything."""
    _git(tmp_path, "init", "-q")
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    mod = pkg / "mod.py"
    mod.write_text("x = 1\n")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    mod.write_text(
        "import jax\ndef g(x):\n    return jax.jit(lambda v: v)(x)\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "tools.dklint", ".", "--no-baseline",
         "--root", str(pkg), "--since", "HEAD", "--format", "json"],
        cwd=pkg, env=env, capture_output=True, text=True,
    )
    assert out.returncode == 1, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert [(f["path"], f["rule"]) for f in payload] == [("mod.py", "DK102")]


def test_cli_since_follows_renames(tmp_path):
    """A file renamed since the ref must lint under its *new* path — the
    pre-rename diff leg dropped renamed files silently (no R-row parsing)."""
    _git(tmp_path, "init", "-q")
    old = tmp_path / "old_name.py"
    old.write_text(
        "import jax\ndef f(x):\n    return jax.jit(lambda v: v)(x)\n"
    )
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    _git(tmp_path, "mv", "old_name.py", "new_name.py")
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "tools.dklint", ".", "--no-baseline",
         "--root", str(tmp_path), "--since", "HEAD", "--format", "json"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
    )
    assert out.returncode == 1, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert [(f["path"], f["rule"]) for f in payload] == [
        ("new_name.py", "DK102")
    ]


def test_changed_files_reports_both_sides_of_a_rename(tmp_path):
    from tools.dklint.cli import changed_files

    _git(tmp_path, "init", "-q")
    (tmp_path / "a.py").write_text("x = 1\n")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    _git(tmp_path, "mv", "a.py", "b.py")
    changed = changed_files(str(tmp_path), "HEAD")
    assert {"a.py", "b.py"} <= changed


def test_analyze_jobs_matches_sequential():
    """--jobs fan-out must be invisible in the output: identical findings,
    identical order."""
    seq, _ = analyze([FIXTURES], root=REPO_ROOT)
    par, _ = analyze([FIXTURES], root=REPO_ROOT, jobs=2)
    assert par == seq
    assert seq  # non-vacuous: the fixture tree fires plenty


def test_cli_since_bad_ref_is_usage_error(tmp_path):
    _git(tmp_path, "init", "-q")
    (tmp_path / "mod.py").write_text("x = 1\n")
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "tools.dklint", ".", "--no-baseline",
         "--root", str(tmp_path), "--since", "no-such-ref"],
        cwd=tmp_path, env=env, capture_output=True, text=True,
    )
    assert out.returncode == 2
    assert "--since" in out.stderr


# ------------------------------------------------- DK123–DK126 shape rules

def test_dk123_shard_spec_fixture():
    got, _ = _run("dk123_shard_specs.py", ["DK123"])
    assert got == [
        ("DK123", 16),  # wrong-rank in_specs vs rank-2 operand
        ("DK123", 20),  # axis absent from governing mesh
        ("DK123", 26),  # duplicate axis in one PartitionSpec
        ("DK123", 42),  # dp=2 provably does not divide 7
        ("DK123", 48),  # 3 in_specs entries, 2 operands
    ]


def test_dk123_no_fp_and_suppression():
    got, _ = _run("dk123_shard_specs.py", ["DK123"])
    lines = [ln for _, ln in got]
    assert 35 not in lines  # sound specs: dp|6, tp|16
    assert 56 not in lines  # single-spec pytree prefix is legal
    assert 62 not in lines  # trailing disable directive
    assert 63 not in lines


def test_dk123_compat_partial_manual_fixture():
    """compat.shard_map sites get the direct call's axis checks, and a
    partial-manual map (axis_names a strict subset of the mesh axes, the
    pipeline x tensor-parallel composition) is valid: not a finding."""
    got, _ = _run("dk123_compat_partial.py", ["DK123"])
    assert got == [
        ("DK123", 37),  # compat path runs the same axis checks as direct
        ("DK123", 44),  # ... including through an import alias
    ]


def test_dk123_nested_mapper_shadowed_axis():
    """shard_map under vmap with a shadowed axis name: the vmap binding
    must not confuse the mesh judgement in either direction, and
    compat.shard_map resolves to the same judgement as direct shard_map."""
    got, _ = _run("dk123_nested_mappers.py", ["DK123"])
    assert got == [
        ("DK123", 35),  # bad spec is still flagged under the shadow
        ("DK123", 48),  # direct shard_map: wrong-rank
        ("DK123", 48),  # compat.shard_map: same finding, same line
    ]
    # the sound nested case (vmap axis_name == mesh axis) stays silent
    assert all(ln > 30 for _, ln in got)


def test_dk123_nested_mapper_dk108_interplay():
    """DK108 must still accept the collective inside the nested mapper —
    the axis is bound by both the mesh and the vmap."""
    got, _ = _run("dk123_nested_mappers.py", ["DK108"])
    assert got == []


def test_dk124_collective_shapes_fixture():
    got, _ = _run("dk124_collective_shapes.py", ["DK124"])
    assert got == [
        ("DK124", 14),  # all_gather dim index out of range
        ("DK124", 19),  # psum_scatter dim index out of range
        ("DK124", 24),  # axis size 4 does not divide scattered dim 6
        ("DK124", 28),  # ppermute duplicate source
        ("DK124", 32),  # ppermute index outside axis size
    ]


def test_dk124_no_fp_and_suppression():
    got, _ = _run("dk124_collective_shapes.py", ["DK124"])
    lines = [ln for _, ln in got]
    for good_line in (37, 38, 39, 40, 41, 46):
        assert good_line not in lines


def test_dk124_same_module_axis_size_conflict(tmp_path):
    """Two literal mesh constructions sizing the same axis differently in
    one (non-test) module is the cross-engine size-conflict smell."""
    mod = tmp_path / "sizes.py"
    mod.write_text(
        "import jax\n"
        "import numpy as np\n"
        "from jax.sharding import Mesh\n"
        "\n"
        "A = Mesh(np.array(jax.devices()).reshape(4, 2), ('dp', 'tp'))\n"
        "B = Mesh(np.array(jax.devices()).reshape(2, 4), ('dp', 'tp'))\n"
    )
    findings, _ = analyze([str(mod)], root=str(tmp_path), select=["DK124"])
    assert [(f.rule, f.line) for f in findings] == [
        ("DK124", 5),  # anchored on the first construction of the axis
        ("DK124", 5),  # once per conflicted axis (dp and tp)
    ]


def test_dk125_pallas_fixture():
    got, _ = _run("dk125_pallas.py", ["DK125"])
    assert got == [
        ("DK125", 17),  # kernel stores float16, out_shape says float32
        ("DK125", 22),  # in_specs block does not divide dim
        ("DK125", 22),  # ... and out_specs likewise
        ("DK125", 33),  # grid x block covers 64 of 128 (in_specs)
        ("DK125", 33),  # ... and out_specs likewise
        ("DK125", 44),  # kernel arity vs in+out+scratch refs
        ("DK125", 55),  # out_specs / out_shape pairing
        ("DK125", 67),  # block rank vs array rank
    ]


def test_dk125_no_fp():
    got, _ = _run("dk125_pallas.py", ["DK125"])
    lines = [ln for _, ln in got]
    # the flash-attention-style sound call and the symbolic one stay silent
    assert all(ln <= 67 for ln in lines), lines


def test_dk126_sharding_drift_fixture():
    got, _ = _run("dk126_sharding_drift.py", ["DK126"])
    assert got == [
        ("DK126", 16),  # device_put P('dp') into shard_map P(None,'tp')
        ("DK126", 22),  # with_sharding_constraint P('tp') into P('dp')
        ("DK126", 41),  # jit in_shardings drift
    ]


def test_dk126_no_fp_and_suppression():
    got, _ = _run("dk126_sharding_drift.py", ["DK126"])
    lines = [ln for _, ln in got]
    assert 30 not in lines  # same axis set: no drift
    assert 36 not in lines  # replicated producer entering a mesh is normal
    assert 47 not in lines  # trailing disable directive


def test_shapes_report_cli():
    """--shapes-report emits the per-engine layout table: engine buckets,
    shard_map rows with resolved specs, deterministic output."""
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "tools.dklint", "distkeras_tpu",
         "--root", REPO_ROOT, "--shapes-report"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "dkshape layout report" in out.stdout
    for bucket in ("engine", "gspmd", "pipeline", "serving"):
        assert f"==== {bucket} ====" in out.stdout
    assert "shard_map[compat]" in out.stdout
    assert "pallas_call" in out.stdout
    # deterministic: a second run is byte-identical (report is an artifact)
    again = subprocess.run(
        [sys.executable, "-m", "tools.dklint", "distkeras_tpu",
         "--root", REPO_ROOT, "--shapes-report"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert again.stdout == out.stdout


def test_cli_stale_warning_in_every_format_and_select_scoped(tmp_path):
    """CI greps the --format github legs for "stale baseline entry", so
    the warning must reach stderr in non-text formats too; a --select
    run must NOT call other rules' entries stale (it produced no
    findings for them, so their staleness is undecidable)."""
    src = "import jax\ndef f(x):\n    return jax.jit(lambda v: v)(x)\n"
    (tmp_path / "mod.py").write_text(src)
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({
        "version": 1,
        "findings": [
            {"path": "mod.py", "rule": "DK102",
             "text": "this line is long gone", "reason": "stale"},
        ],
    }))
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)

    def run(*extra):
        return subprocess.run(
            [sys.executable, "-m", "tools.dklint", "mod.py",
             "--root", str(tmp_path), "--baseline", str(baseline), *extra],
            cwd=tmp_path, env=env, capture_output=True, text=True,
        )

    for fmt in ("github", "sarif", "json", "text"):
        got = run("--format", fmt)
        assert "stale baseline entry" in got.stderr, (fmt, got.stderr)
    # DK101 selected: the DK102 entry's staleness is out of scope
    scoped = run("--select", "DK101")
    assert "stale baseline entry" not in scoped.stderr, scoped.stderr
    # ...but a select that covers the entry's rule still reports it
    covered = run("--select", "DK102")
    assert "stale baseline entry" in covered.stderr, covered.stderr
