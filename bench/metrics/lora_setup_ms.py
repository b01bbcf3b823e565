"""Per experiment, the program's ``fed.setup`` span on the LLM route
(``simulate_llm``: the corpus sharded and sent to the device, the scan
built): ``experiment_setup_ms``'s reading of the granite cell."""

from bench.metrics.experiment_setup_ms import read  # noqa: F401
