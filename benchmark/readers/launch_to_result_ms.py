"""launch_to_result_ms: program span coalescer.device (launch -> result on the host: queueing on the chip behind earlier launches + the kernel + the fetch), median."""

import measure


def read(run):
    return measure.span_median_ms(run, "coalescer.device")
