"""Device idle share over the traced window of solves, in %: one minus
the union of the device-operation intervals over the window's length."""


def read(view):
    return 100.0 * (1.0 - view.busy_s / view.window_s)
