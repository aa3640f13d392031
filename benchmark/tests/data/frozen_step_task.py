"""The training task with the timed path broken underneath: the step
returns its state unchanged (losses and gradients still flow). The check
has to come out incorrect."""

import runpy

from tony_tpu import train

_make = train.make_train_step


def frozen(*args, **kwargs):
    step = _make(*args, **kwargs)

    def stepper(state, batch):
        import jax

        keep = jax.tree.map(lambda a: a.copy(), state.params)
        new, metrics = step(state, batch)
        return new.replace(params=keep), metrics
    return stepper


train.make_train_step = frozen
runpy.run_path(__file__.replace("train_task.py", "real_task.py"),
               run_name="__main__")
