from repro.data.synthetic import (lm_member_datasets, image_member_datasets,
                                  batch_indices, relabel_indices,
                                  gather_members, sample_batch,
                                  sample_relabel_subset)

__all__ = ["lm_member_datasets", "image_member_datasets", "batch_indices",
           "relabel_indices", "gather_members", "sample_batch",
           "sample_relabel_subset"]
