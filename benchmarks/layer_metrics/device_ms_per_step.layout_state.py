"""Self time on chip 0, in the traced window, of the step program's
`layout` instructions whose `source` is `state`, per step: what the
step pays EVERY step to re-lay or move a parameter of the step (a
weight, an optimizer moment, a feed), an array that does not change
between one step's end and the next one's start.  Part of
`device_ms_per_step.layout`."""

import layout_owner

META = {"layer": "ops", "unit": "ms", "moves": "mfu",
        "source": "device_trace", "cells": None}


def compute(run):
    a = layout_owner.owned_anatomy(run)
    return None if a is None else layout_owner.layout_state_ms_per_step(a)
