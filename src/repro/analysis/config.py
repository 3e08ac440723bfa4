"""Per-family path scoping and the corpus layout of the kernel contract.

Scoping is prefix-based over posix paths relative to the analysis root
(the repo root in CI and the tier-1 self-scan).  The defaults encode this
repo's layout and failure history:

* **determinism** rules cover the simulation path — every module whose
  output feeds a canonical trace or a ``FleetReport`` — and deliberately
  exclude the wall-clock-legitimate packages (``benchmarks/``,
  ``train/``, ``launch/``: real timing is their job).
* **locks** rules are annotation-driven (they fire only where a
  ``# guarded-by:`` tag exists), so they scope to all of ``src``.
* **kernel-contract** rules read a fixed corpus: the Pallas kernel
  modules, their oracle module, the dispatch module, and the parity test
  file that the ``kernel-parity`` CI job runs.
* **tracing** rules cover every module that defines ``jax.jit``-compiled
  functions on the sim/kernel path.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Scope:
    """Path prefixes a rule family applies to (exclude wins over include)."""

    include: tuple[str, ...]
    exclude: tuple[str, ...] = ()

    def matches(self, rel: str) -> bool:
        if any(rel == e or rel.startswith(e) for e in self.exclude):
            return False
        return any(rel == i or rel.startswith(i) for i in self.include)


@dataclasses.dataclass(frozen=True)
class KernelContractConfig:
    """File layout of the kernel/oracle/dispatch/parity-test contract."""

    kernels_dir: str = "src/repro/kernels"
    ops_module: str = "src/repro/kernels/ops.py"
    ref_module: str = "src/repro/kernels/ref.py"
    # Parity tests must live in the file(s) the kernel-parity CI job runs —
    # a passing test elsewhere does not keep kernel/oracle drift attributable.
    test_files: tuple[str, ...] = ("tests/test_kernels.py",)
    # Infrastructure modules in kernels_dir that are not kernels themselves.
    non_kernel_modules: tuple[str, ...] = ("__init__.py", "ops.py", "ref.py")


@dataclasses.dataclass(frozen=True)
class ParityConfig:
    """Layout of the dual-engine parity contract (PAR* rules): the
    canonical module holding the shared aggregation functions, the engine
    modules required to route through them, and the prefix under which
    drift copies are hunted."""

    canonical_module: str = "src/repro/core/fleet.py"
    engine_modules: tuple[str, ...] = (
        "src/repro/core/fleet.py",
        "src/repro/core/engine/vectorized.py",
        "src/repro/core/engine/sharded.py",
    )
    shared_functions: tuple[str, ...] = (
        "predict_demands",
        "auto_concurrency",
        "single_tenant_optimum",
        "assemble_fleet_report",
    )
    #: The funnel every engine's run path must actually call.
    required_calls: tuple[str, ...] = ("assemble_fleet_report",)
    watch_prefix: str = "src/"


@dataclasses.dataclass(frozen=True)
class AnalysisConfig:
    scopes: dict = dataclasses.field(default_factory=dict)
    kernel_contract: KernelContractConfig = dataclasses.field(
        default_factory=KernelContractConfig
    )
    parity: ParityConfig = dataclasses.field(default_factory=ParityConfig)

    def scope_for(self, family: str) -> Scope:
        return self.scopes.get(family, Scope(include=("",)))  # default: all


#: The sim path: modules whose behaviour must be a pure function of seeds
#: and simulated time.  PR 2 (wall-clock admission races), PR 3 (unseeded
#: refit regions), and PR 5 (stale shared-link intervals) were all runtime
#: manifestations of conventions these prefixes now have checked statically.
SIM_PATH = (
    "src/repro/core/",
    "src/repro/netsim/",
    "src/repro/testing/",
)

#: jit-compiled sim/kernel modules: Python control flow on traced values or
#: state mutation under ``jax.jit`` either fails at runtime on real inputs
#: or silently bakes one branch into the compiled artifact.
TRACED_PATH = (
    "src/repro/core/batched.py",
    "src/repro/core/clustering.py",
    "src/repro/core/spline.py",
    "src/repro/kernels/",
    "src/repro/dist/",
)


def default_config() -> AnalysisConfig:
    return AnalysisConfig(
        scopes={
            "determinism": Scope(include=SIM_PATH),
            "locks": Scope(include=("src/",)),
            "tracing": Scope(include=TRACED_PATH),
            # the suffix convention is load-bearing where the transfer math
            # lives; CLI/launch glue may name things loosely
            "units": Scope(include=("src/repro/core/", "src/repro/netsim/")),
            # meta rules (suppression hygiene) apply wherever suppressions do
            "meta": Scope(include=("src/", "tests/", "benchmarks/")),
        },
        kernel_contract=KernelContractConfig(),
        parity=ParityConfig(),
    )


def permissive_config() -> AnalysisConfig:
    """Everything in scope — used by fixture tests and ad-hoc CLI runs on
    out-of-tree files."""
    return AnalysisConfig(scopes={}, kernel_contract=KernelContractConfig())
