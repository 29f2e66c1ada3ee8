"""Arrival process ``poisson``: exponential gaps at ``rate`` a second, drawn
independently or stratified (``traffic.poisson_arrivals``)."""

from benchmark import traffic


def arrivals(rng, rate, seconds, sampling, mix):
    return traffic.poisson_arrivals(rng, rate, seconds, sampling)
