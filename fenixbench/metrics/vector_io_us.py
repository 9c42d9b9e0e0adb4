"""Device time per scan step of the Vector I/O FIFO: enqueue of granted
packets and dequeue of the Model Engine's lanes (scopes
``fenix_vio_enqueue``, ``fenix_vio_dequeue``)."""

from fenixbench import spans


def read(ctx):
    return spans.stage_us(ctx, ("vio_enqueue", "vio_dequeue"))
