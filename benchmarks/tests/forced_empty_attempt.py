"""One traced run of `fanin16` whose FIRST attempt is forced empty (run on
the chip, PR 41: none of nine traced runs needed a second attempt, so the
retry was shown working this way). The first watch under the profiler
returns at once: the profiler is stopped `hold_s` after it came on, on a
device that had been quiet for `quiet_s`. Everything after that is the
driver's own: the judge on a real empty trace, the directory's removal, a
second profiler session in the same process, the second attempt's stretch.

    python3 benchmarks/tests/forced_empty_attempt.py --seed <n>

from the root of a checkout; further arguments are run.py's."""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
from drivers import serve_tenants  # noqa: E402


def main(argv) -> int:
    watch, cut = serve_tenants._watch, []

    def cut_the_first(until, quiet_s=None):
        if quiet_s is None and not cut:
            cut.append(until)
            return time.monotonic()
        return watch(until, quiet_s)

    serve_tenants._watch = cut_the_first
    cell = ["--workload", "serve-mpt-tenants-1chip.fanin16", "--seconds", "51", "--trace", "1"]
    return run.main(cell + argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
