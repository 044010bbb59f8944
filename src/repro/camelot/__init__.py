# repro.camelot — the declarative control plane over the Camelot runtime.
#
# The public front door of the reproduction: describe WHAT/WHERE/HOW-WELL
# with frozen specs (ServiceSpec / ClusterSpec / QoSSpec, dict
# round-trippable), drive the whole lifecycle through one CamelotSession
# (profile -> solve -> simulate -> serve -> reallocate), and pick solvers
# from the pluggable policy registry (max-peak, min-resource, even,
# standalone, laius, camelot-nc — register_policy adds more).
#
#   specs.py    — ServiceSpec / ClusterSpec / QoSSpec / LoadSpec
#   policies.py — Policy protocol, registry, built-in policies
#   session.py  — CamelotSession facade
#
# The internal layers (repro.core.*, repro.sim.*, repro.serving.*) remain
# importable and unchanged; the facade only wires them.
from repro.camelot.specs import (DEVICE_KINDS, KNOWN_DEVICES, ClusterSpec,
                                 LoadSpec, MultiServiceSpec, QoSSpec,
                                 ServeSpec, ServiceSpec, SolverSpec,
                                 TenantSpec, device_for_kind)
from repro.camelot.policies import (BaselinePolicy, MaxPeakPolicy,
                                    MinResourcePolicy, Policy,
                                    UnknownPolicyError, available_policies,
                                    get_policy, register_policy)
from repro.camelot.session import CamelotSession, MultiServiceSession
from repro.core.allocator import SAConfig, SolveResult
from repro.core.lifecycle import (AdmissionDecision, AdmissionQuote,
                                  LifecycleEvent, LifecycleManager)

__all__ = [
    "DEVICE_KINDS", "KNOWN_DEVICES", "device_for_kind", "ClusterSpec",
    "LoadSpec", "MultiServiceSpec", "QoSSpec", "ServeSpec", "ServiceSpec",
    "SolverSpec", "TenantSpec", "BaselinePolicy",
    "MaxPeakPolicy", "MinResourcePolicy", "Policy", "UnknownPolicyError",
    "available_policies", "get_policy", "register_policy", "CamelotSession",
    "MultiServiceSession", "SAConfig", "SolveResult",
    "AdmissionDecision", "AdmissionQuote", "LifecycleEvent",
    "LifecycleManager",
]
