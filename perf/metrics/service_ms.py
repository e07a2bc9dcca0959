"""Engine: mean launch-plus-readback milliseconds per launch
(``Ticket.service_s``)."""
UNIT = "ms"


def read(layer, spec):
    launches = layer.get("launches")
    if not launches:
        return None
    return 1e3 * sum(l.t_done - l.t_launch for l in launches) / len(launches)
