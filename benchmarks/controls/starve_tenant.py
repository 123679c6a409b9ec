"""Control: one tenant's admission lane starved. A witness job of the
victim (the last, by name, of the tenants the program has served so far) is
kept out of the scheduler until `OVERTAKEN_BY` witness jobs of other tenants
have been admitted behind it: a lane that is served only after the others
have had two whole rounds of twelve. It breaks the guarantee that no tenant
is starved (a configuration whose clients post under `X-Phant-Tenant`,
`serve-mpt-tenants-1chip`): every answer is still right, and a run under it
must come out not correct by `tenant_least_over_most` alone.

The hold ends by itself after `HELD_AT_MOST_S`, so that the victim's last
requests are answered when the others have stopped posting."""

import threading

OVERTAKEN_BY = 24
HELD_AT_MOST_S = 10.0


def apply(log):
    """Returns the call that takes the fault out again."""
    from phant_tpu.serving.qos import DEFAULT_TENANT, OVERFLOW_TENANT
    from phant_tpu.serving.scheduler import VerificationScheduler
    from phant_tpu.utils.trace import metrics

    served = (k for k in metrics.snapshot()["counters"] if k.startswith("sched.tenant_served{"))
    tenants = sorted({k.split('"')[1] for k in served} - {DEFAULT_TENANT, OVERFLOW_TENANT})
    if not tenants:
        raise SystemExit("starve_tenant: the warm-up posted under no tenant: nothing to starve")
    victim, sound = tenants[-1], VerificationScheduler._admit
    passed, others = threading.Condition(), [0]

    def _admit(self, job, wait_for_space):
        if job.kind == "witness":
            with passed:
                if job.tenant == victim:
                    mark = others[0] + OVERTAKEN_BY
                    passed.wait_for(lambda: others[0] >= mark, timeout=HELD_AT_MOST_S)
                else:
                    others[0] += 1
                    passed.notify_all()
        return sound(self, job, wait_for_space)

    VerificationScheduler._admit = _admit
    log(
        f"CONTROL starve_tenant: a witness job of tenant {victim} is admitted after "
        f"{OVERTAKEN_BY} of the other tenants' ({tenants[:-1]})"
    )
    return lambda: setattr(VerificationScheduler, "_admit", sound)
