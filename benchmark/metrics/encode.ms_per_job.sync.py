"""Host encode stage per device job, in ms: the pool's
``encode_time_seconds`` histogram (``verify.encode_job``:
expand_message_xmd, field draws, limb packing, transfer), sum over count of
the observations made in the window.  In range sync the chunks of one call
run encode and device one after the other, so this time adds to every
chunk."""

HISTOGRAM = "lodestar_tpu_bls_pool_encode_time_seconds"


def read(run):
    mean = run.histogram_mean(HISTOGRAM)
    return None if mean is None else mean * 1e3
