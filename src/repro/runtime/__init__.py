from repro.runtime.train_loop import (AdaptiveController, AdaptiveOptions,
                                      TrainOptions, abstract_state,
                                      init_state, make_train_step, train,
                                      train_state_shardings)

__all__ = ["AdaptiveController", "AdaptiveOptions", "TrainOptions",
           "abstract_state", "init_state", "make_train_step", "train",
           "train_state_shardings"]
