"""Blocking device-to-host reads per cloud in the plan build: the
program's ``plan.host_sync`` counter over the clouds served.

Both counts run from process start, so the warm-up tick's clouds are in
both; every cloud of a traffic mix is fresh geometry and makes the same
reads, so the ratio is the window's."""
from repro.runtime import guard


def read(ctx):
    syncs = guard.health().get("plan.host_sync")
    clouds = guard.health().get("serve.completed")
    if not syncs or not clouds:
        return None
    return syncs / clouds
