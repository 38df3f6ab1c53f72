"""Signature sets per device job in the window: the pool's
``sig_sets_total`` over ``jobs_started``.  The governor's chunk width sets
it; a wider job pays the program's fixed floor fewer times."""


def read(run):
    jobs = run.delta("lodestar_tpu_bls_pool_jobs_started_total")
    return run.delta("lodestar_tpu_bls_pool_sig_sets_total") / jobs if jobs else None
