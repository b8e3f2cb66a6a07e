"""EDL401 triggering fixture: telemetry counter/gauge-name typos."""


class Frontend(object):
    def __init__(self, telemetry):
        self.telemetry = telemetry
        self._telemetry = telemetry

    def admit(self):
        # typo'd counter: forks a new counter silently -> EDL401
        self.telemetry.count("admittd")

    def reject(self):
        self._telemetry.count("rejectd", 2)  # EDL401 (underscored attr)

    def depth(self):
        # typo'd gauge: forks a dead TB tag + Prometheus series -> EDL401
        self.telemetry.gauge("queue_dept", 3)


def module_level(router_telemetry):
    router_telemetry.count("breaker_tripz")  # EDL401 (bare receiver)
    router_telemetry.gauge("healthy_replica", 1)  # EDL401 (gauge typo)


def slow(telemetry):
    # typo'd slow cause: forks a labeled series no cause taxonomy
    # consumer will ever aggregate -> EDL401
    telemetry.count_slow_cause("queue_wiat")


def health(telemetry):
    # typo'd runtime-health counter (steady_recompiles): the anomaly
    # count would fork and the zero-recompile test would watch a
    # dead series -> EDL401
    telemetry.count("steady_recompile")
    # typo'd runtime-health gauge (last_progress_age_ms): the
    # autoscaler's self-report signal would scrape a dead series
    # -> EDL401
    telemetry.gauge("last_progress_age", 120.0)
