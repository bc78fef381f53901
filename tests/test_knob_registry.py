"""Static knob-drift check (ISSUE 9 satellite): every ``MXNET_*`` env
var the package reads must be registered in ``config.KNOBS``.

Knob drift has bitten twice (undocumented env reads with silently
different defaults per call site); this test greps the package source
for MXNET_* string literals and fails when one is neither registered
nor on the documented allowlist, so the NEXT drift fails in CI instead
of in a job.
"""
import os
import re

from mxnet_tpu import config

PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "mxnet_tpu")

# Per-role process-IDENTITY env the launcher/tracker contract sets for
# each spawned process (rank, topology, rendezvous address). These are
# not user-tunable knobs — they are the DMLC_*-style wiring documented
# in tools/launch.py (--launcher manual prints them per role) — so they
# live outside the KNOBS registry on purpose.
ALLOWLIST = {
    "MXNET_TPU_NUM_WORKERS",
    "MXNET_TPU_WORKER_ID",
    "MXNET_TPU_WORKER_RANK",
    "MXNET_TPU_COORDINATOR",
    "MXNET_KVSTORE_SERVER",
}

_NAME = re.compile(r"""["'](MXNET_[A-Z][A-Z0-9_]*)["']""")


def _package_env_names():
    names = {}
    for dirpath, _dirnames, filenames in os.walk(PKG):
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
            for m in _NAME.finditer(src):
                name = m.group(1)
                if name.endswith("_"):
                    continue  # a prefix filter string, not an env read
                names.setdefault(name, set()).add(
                    os.path.relpath(path, PKG))
    return names


def test_every_env_read_is_registered_or_allowlisted():
    unknown = {
        name: sorted(files)
        for name, files in _package_env_names().items()
        if name not in config.KNOBS and name not in ALLOWLIST
    }
    assert not unknown, (
        "unregistered MXNET_* env reads (add them to config.KNOBS with "
        "a default + status + reader citation, or — ONLY for "
        "launcher-contract identity vars — to the test allowlist): %r"
        % unknown)


def test_allowlist_entries_are_still_in_use():
    used = _package_env_names()
    stale = sorted(n for n in ALLOWLIST if n not in used)
    assert not stale, (
        "allowlist entries no longer read anywhere — remove them: %r"
        % stale)


def test_autoscale_and_qos_knobs_are_registered():
    """The ISSUE 18 knob surface, by name: the autoscaler's control
    loop and the tenant QoS grammar are operator-facing — a rename
    that forgets the registry entry must fail here, not in a fleet."""
    for name in ("MXNET_FLEET_AUTOSCALE_INTERVAL",
                 "MXNET_FLEET_AUTOSCALE_MIN",
                 "MXNET_FLEET_AUTOSCALE_MAX",
                 "MXNET_FLEET_AUTOSCALE_UP_LOAD",
                 "MXNET_FLEET_AUTOSCALE_DOWN_LOAD",
                 "MXNET_FLEET_AUTOSCALE_HYSTERESIS",
                 "MXNET_FLEET_AUTOSCALE_COOLDOWN",
                 "MXNET_FLEET_AUTOSCALE_SLO_MS",
                 "MXNET_QOS_TENANTS",
                 "MXNET_QOS_DEFAULT_PRIORITY",
                 "MXNET_QOS_BURST_SECONDS"):
        assert name in config.KNOBS, name
        assert config.KNOBS[name][1] == "honored", name


def test_train_pass_knobs_are_registered():
    """The remat mode is operator-facing — a rename that forgets the
    registry entry must fail here, not in a job. The pass-list knob and
    the layout kill switch went with the training-graph passes (PR 32):
    a TrainStep compiles the graph it is given."""
    assert config.KNOBS["MXNET_TPU_REMAT"][1] == "honored"
    for name in ("MXNET_IR_TRAIN_PASSES", "MXNET_IR_LAYOUT"):
        assert name not in config.KNOBS, name


def test_new_self_healing_knobs_are_registered():
    """The ISSUE 9 knob surface, by name (a rename that forgets the
    registry entry must fail here, not in a job)."""
    for name in ("MXNET_TPU_SENTINEL", "MXNET_TPU_GUARD",
                 "MXNET_TPU_GUARD_CONSEC", "MXNET_TPU_GUARD_SPIKE",
                 "MXNET_TPU_GUARD_BACKOFF", "MXNET_TPU_GUARD_BUDGET",
                 "MXNET_TPU_GUARD_INTERVAL", "MXNET_PREEMPT_GRACE"):
        assert name in config.KNOBS, name
        assert config.KNOBS[name][1] == "honored", name
