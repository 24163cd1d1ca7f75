"""Share of the rows the step program ran that carried no work, in
percent, over the window's steps: 1 minus the engine's ``rows_useful``
(active decode slots and valid chunk rows) over its ``rows_computed``
(every decode slot and each lane's whole bucket), each summed over the
steps' counter changes."""
from sartbench.stats import counter_sum, window_steps


def read(run):
    steps = window_steps(run)
    computed = counter_sum(run, steps, "rows_computed")
    useful = counter_sum(run, steps, "rows_useful")
    if not computed or useful is None:
        return None
    return 100.0 * (1.0 - useful / computed)
