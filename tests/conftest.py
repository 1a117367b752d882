import os
import sys
import tomllib

# Tests run on the default single CPU device (the dry-run subprocesses set
# their own XLA_FLAGS); keep JAX quiet and deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# tests/ is not a package; make _hypothesis_compat importable regardless of
# the pytest import mode in use.
sys.path.insert(0, os.path.dirname(__file__))

_PYPROJECT = os.path.join(os.path.dirname(__file__), "..", "pyproject.toml")


def _hypothesis_config() -> dict:
    """The [tool.repro.hypothesis] table from pyproject.toml."""
    defaults = {"profile": "repro-ci", "seed": 20260808,
                "max_examples": 10, "derandomize": True, "print_blob": True}
    with open(_PYPROJECT, "rb") as f:
        table = tomllib.load(f).get("tool", {}).get("repro", {}) \
                               .get("hypothesis", {})
    defaults.update(table)
    return defaults


_CFG = _hypothesis_config()
# Pinned property-test seed: env wins, pyproject supplies the default.  The
# shim (tests/_hypothesis_compat.py) reads the env var, so publish whichever
# value won before test modules import it.
PINNED_SEED = int(os.environ.get("REPRO_HYPOTHESIS_SEED", _CFG["seed"]))
os.environ["REPRO_HYPOTHESIS_SEED"] = str(PINNED_SEED)

try:  # register/load the deterministic profile on real hypothesis only
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile(
        _CFG["profile"],
        derandomize=bool(_CFG["derandomize"]),
        print_blob=bool(_CFG["print_blob"]),
        deadline=None,
        max_examples=int(_CFG["max_examples"]),
    )
    _hyp_settings.load_profile(_CFG["profile"])
    _HYPOTHESIS = "hypothesis"
except ModuleNotFoundError:
    _HYPOTHESIS = "compat shim"


def pytest_report_header(config):
    return (f"repro property tests: {_HYPOTHESIS}, "
            f"profile={_CFG['profile']}, seed={PINNED_SEED} "
            f"(override with REPRO_HYPOTHESIS_SEED)")


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    # On failure, print the seed needed to reproduce the property-test draws.
    if terminalreporter.stats.get("failed") or terminalreporter.stats.get(
            "error"):
        terminalreporter.write_line(
            f"property-test seed: REPRO_HYPOTHESIS_SEED={PINNED_SEED} "
            f"(profile {_CFG['profile']}) — rerun with this env var to "
            "reproduce the same draws")
