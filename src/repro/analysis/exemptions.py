"""Pinned `# analysis: host-ok` exemption inventory (DESIGN.md §14).

The trace-safety lint lets a genuine host path escape with an
`# analysis: host-ok <why>` comment. That is the right local mechanism
— but silently accumulating exemptions would erode the gate one
innocent-looking comment at a time. So the COUNT is pinned here: the
CLI's default run collects the full inventory
(`trace_lint.collect_host_ok` over the default lint dirs), publishes
every site in the JSON report (`host_ok.sites`), and emits a
`host-ok-drift` warning-severity finding when the count moves — strict
mode (the CI gate) fails on it, a plain run only reports it.

Adding or removing a host-ok comment is therefore a two-line change by
design: the comment itself (with its justification) AND this pin. The
diff makes the new host escape visible to review instead of burying it
in a comment.
"""
from __future__ import annotations

# number of `# analysis: host-ok` comments under the default lint dirs
# (src/repro/{core,kernels,launch,service,train,checkpoint}); PR 10
# added 11: the fault layer (core/faults.py — deterministic verdicts,
# counters, CLI spec parsing), the bulletin-board transport
# (service/transport.py — the device->host announcement boundary), and
# the crash-safe resume path (driver min_round pull, chain.head_round,
# store.steps filename parsing). Two went since: the service driver
# hands the announcing mask to `transport.collect`, which pulls it
# under its own exemption
EXPECTED_HOST_OK = 37
