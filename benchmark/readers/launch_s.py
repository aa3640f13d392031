"""`tony submit` -> ALL_TASKS_RUNNING in the job's event log: the control
plane's share of set-up."""


def read(art: dict, args: dict):
    if art.get("t_all_running") is None:
        return None
    return art["t_all_running"] - art["t_submit"]
