"""Model FLOPs of the prompts prefilled and the tokens decoded in the
traced window (from the configuration's shapes) over the window times the
chip's peak, in percent."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.model_flops() / (run.trace["window_s"]
                                        * run.peaks["flops"])
