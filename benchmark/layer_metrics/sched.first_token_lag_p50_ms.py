"""Admit dispatched to first token PROCESSED at the host mirror's drain point
(``RequestResult.host_s``), median over the requests due in the window: the
ring's per-request ``first_token_lag`` phase span — the lag-one protocol reads
an admit's token one event behind, so this is about one decode block."""
from benchmark import spans


def read(run):
    return spans.request_median_ms(run, "first_token_lag", cat="phase")
