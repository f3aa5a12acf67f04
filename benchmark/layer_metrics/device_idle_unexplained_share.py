"""``device_idle_unexplained_share``: share of the device's idle time in the
traced slice during which no ``wf.*`` span of the program was open on any
thread: what of the idle time no span of the program accounts for."""

import span_reduce


def read(run):
    return span_reduce.idle_unexplained_share(run)
