"""90th percentile of the result delay over the closes due in the window
(see delay.py): with s/2 closes in s seconds, the third-highest of ~22. Its
runs spread by 30 % of their median (PR 23), too wide for a bound, so it
stands beside the median as a layer metric."""

import delay


def read(run):
    return delay.percentile(delay.delays_ms(run), 0.90)
