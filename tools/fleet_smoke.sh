#!/bin/bash
# Fleet control-plane smoke — the tier-1 gate shape of
# tools/fleet_harness.py (ISSUE 12): a bounded replay through a
# supervised in-process fleet PLUS a 2-replica real-process fleet,
# with one replica kill, one SIGKILLed replica server process
# (supervision restarts it, the prober readmits it), and one primary-
# router kill per phase (standby takeover), gated on the SLOs: zero
# lost/duplicated streams (token-exact vs the fault-free oracle),
# TTFT p99, shed rate, page conservation, and ZERO leaked processes.
#
# CPU-only by construction (the harness forces jax_platforms=cpu and
# workers force it in their own interpreters).  If the timeout ever
# fires, the
# workers' parent-death watchdog self-reaps them within seconds, so
# even the hard-kill path leaves no orphans (round-4 addenda).
set -o pipefail
cd "$(dirname "$0")/.."
timeout -k 10 420 python tools/fleet_harness.py --smoke
