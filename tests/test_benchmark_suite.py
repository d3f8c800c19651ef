"""The yardstick's own tests under tier-1: `benchmark/tests` lies outside
`tests/`, so the manifest checks (every name, unit and `layer` the driver
would refuse), the generators, the costs, the trace reduction and the span
and record readers run here too, fixtures `manifest` and `xl` included. Nothing under
`benchmark/` is edited; `python -m pytest benchmark/tests -q` runs the same."""

from benchmark.tests.test_benchmark import *  # noqa: F401,F403
from benchmark.tests.test_program_spans import *  # noqa: F401,F403
from benchmark.tests.test_record_readers import *  # noqa: F401,F403
