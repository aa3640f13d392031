"""The training task with a program built inside every step, so inside
the window too (as an engine that compiles a small program per new shape
would). The run has to come out incorrect."""

import runpy

from tony_tpu import train

_make = train.make_train_step


def compiling(*args, **kwargs):
    step = _make(*args, **kwargs)

    def stepper(state, batch):
        import jax

        jax.jit(lambda x: x + 1)(0)      # a new function: a new program
        return step(state, batch)
    return stepper


train.make_train_step = compiling
runpy.run_path(__file__.replace("train_task.py", "real_task.py"),
               run_name="__main__")
