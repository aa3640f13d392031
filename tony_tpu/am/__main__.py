"""``python -m tony_tpu.am`` — standalone AM process (reference:
``TonyApplicationMaster.main``, launched in the AM container by the RM on the
client's behalf — SURVEY.md §3.1)."""

import argparse
import signal
import sys

from tony_tpu.am import ApplicationMaster
from tony_tpu.conf import TonyConfig


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tony-am")
    p.add_argument("--conf", required=True, help="serialized job config")
    p.add_argument("--app-id", required=True)
    p.add_argument("--job-dir", required=True)
    p.add_argument("--host", default="127.0.0.1",
                   help="address executors use to reach the AM RPC")
    p.add_argument("--verbose", action="store_true")
    args = p.parse_args(argv)
    conf = TonyConfig.load(args.conf)
    am = ApplicationMaster(conf, app_id=args.app_id, job_dir=args.job_dir,
                           host=args.host, quiet=not args.verbose)
    # Graceful SIGTERM (client kill fallback): drain through the AM's normal
    # teardown instead of dying mid-loop and orphaning executor groups.
    signal.signal(signal.SIGTERM,
                  lambda _sig, _frm: am.request_stop("AM received SIGTERM"))
    try:
        return am.run()
    except Exception as e:  # noqa: BLE001 — AM-internal failure, not job's
        from tony_tpu import constants
        print(f"[tony-am] internal error: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return constants.EXIT_AM_ERROR


if __name__ == "__main__":
    sys.exit(main())
