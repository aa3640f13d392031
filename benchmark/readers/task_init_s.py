"""Task process start -> state built (imports, distributed.initialize,
TPU init, create_train_state), on the task script's own clock."""


def read(art: dict, args: dict):
    task = art.get("task")
    if not task:
        return None
    return task["t_init"] - task["t_process"]
