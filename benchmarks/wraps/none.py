"""The program itself: one device, no wrapping."""

EXPECTS_IN_HLO = []


def wrap(main, startup, loss, n_devices):
    if n_devices != 1:
        raise ValueError("wrap 'none' runs on one device, the cell asks "
                         "for %d" % n_devices)
    return main
