"""Front end: slots used over the C x B slots of each launch, over all
launches."""
UNIT = "%"


def read(layer, spec):
    launches = layer.get("launches")
    if not launches:
        return None
    used = sum(l.slots for l in launches)
    return 100.0 * used / (len(launches) * layer["slots_per_launch"])
