"""Telemetry, online detection, root-cause analysis, fault injection and
the program's own tracing.

The control plane's sensing layer (ROADMAP: "telemetry, fault injection
and self-healing ops"): runtime and simulator producers emit one shared
sample schema onto ``bus.TelemetryBus``; ``detectors.DetectorBank`` turns
the noisy streams into typed manager events; ``rca.RootCauseAnalyzer``
classifies each event into a remediation; ``faults.ChaosHarness`` closes
the loop against injected ground-truth faults.  ``trace`` names the
program's work in a profiler trace.

Import from the submodule that defines a name: the package imports
nothing, so the models can use ``trace`` without loading the control
plane.
"""
