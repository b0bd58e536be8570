"""Install sanity check (reference: python/paddle/fluid/install_check.py
``run_check`` — builds a tiny linear model, runs one train step on the
available device(s), and prints a friendly verdict)."""

import numpy as np

from . import (Program, program_guard, unique_name, Scope, scope_guard,
               Executor, CPUPlace, TPUPlace, layers, optimizer)


def run_check(use_device=None):
    """Train one step of a tiny model; raises on failure, prints success.

    ``use_device``: None (the default backend's first device), "cpu",
    "tpu" (raises when JAX found no TPU).
    """
    place = {None: None, "cpu": CPUPlace(), "tpu": TPUPlace()}[use_device]

    main, startup = Program(), Program()
    with program_guard(main, startup):
        with unique_name.guard():
            x = layers.data(name="ic_x", shape=[4], dtype="float32")
            y = layers.data(name="ic_y", shape=[1], dtype="float32")
            pred = layers.fc(x, size=1)
            loss = layers.mean(layers.square_error_cost(pred, y))
            optimizer.SGD(learning_rate=0.01).minimize(loss)
    rng = np.random.RandomState(0)
    exe = Executor(place)
    with scope_guard(Scope()):
        exe.run(startup)
        lv = exe.run(main,
                     feed={"ic_x": rng.rand(8, 4).astype(np.float32),
                           "ic_y": rng.rand(8, 1).astype(np.float32)},
                     fetch_list=[loss])[0]
    val = float(np.asarray(lv).reshape(-1)[0])
    if not np.isfinite(val):
        raise RuntimeError("install check produced a non-finite loss")
    print("Your paddle_tpu works on %r! loss = %.4f" % (exe.place, val))
    return True
