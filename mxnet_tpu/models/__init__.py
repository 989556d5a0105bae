"""``mxnet_tpu.models``: model families beyond the in-repo gluon zoo
(capability targets from SURVEY.md §2.6: GluonNLP BERT, GluonTS
forecasters; Llama-family stretch)."""
from . import bert
from .bert import BERTModel, BERTForPretrain, bert_base, bert_small, \
    bert_large, get_bert
from . import forecast
from .forecast import DeepAR, TransformerForecaster
from . import llama
from . import ssd
from .ssd import SSD, ssd_tiny, MultiBoxLoss
from .llama import (LlamaModel, LlamaForCausalLM, get_llama,
                    llama_tiny, llama3_8b)
from . import sambay
from .sambay import (SambaYModel, SambaYForCausalLM, get_sambay,
                     sambay_tiny, phi4_mini_flash)
from . import afmoe
from .afmoe import (AfmoeModel, AfmoeForCausalLM, get_afmoe, afmoe_tiny,
                    trinity_large_ep8)
from . import pangu_moe
from .pangu_moe import (PanguMoeModel, PanguMoeForCausalLM, get_pangu_moe,
                        pangu_moe_tiny, pangu_ultra_moe_ep16)
from . import hf_loader
from .hf_loader import (read_safetensors, write_safetensors,
                        load_hf_llama, export_hf_llama,
                        load_hf_bert, export_hf_bert)
from . import nmt
from .nmt import (TransformerNMT, BeamSearchScorer, BeamSearchSampler,
                  get_nmt, nmt_tiny, transformer_en_de_512)
from . import segmentation
from .segmentation import (FCN, DeepLabV3, SegmentationMetric,
                           SoftmaxSegLoss, fcn_tiny, deeplab_tiny)
from . import yolo
from .yolo import YOLOv3, YOLOv3Loss, yolo3_tiny
from . import pose
from .pose import (SimplePose, PoseHeatmapLoss, PCKMetric,
                   simple_pose_tiny)
from . import rcnn
from .rcnn import FasterRCNN, FasterRCNNLoss, faster_rcnn_tiny

__all__ = ["hf_loader", "read_safetensors", "write_safetensors",
           "load_hf_llama", "export_hf_llama", "load_hf_bert",
           "export_hf_bert",
           "ssd", "SSD", "ssd_tiny", "MultiBoxLoss",
           "bert", "BERTModel", "BERTForPretrain", "bert_base",
           "bert_small", "bert_large", "get_bert", "forecast",
           "DeepAR", "TransformerForecaster", "llama", "LlamaModel",
           "LlamaForCausalLM", "get_llama", "llama_tiny", "llama3_8b", "sambay", "SambaYModel",
           "SambaYForCausalLM", "get_sambay", "sambay_tiny",
           "phi4_mini_flash", "afmoe", "AfmoeModel", "AfmoeForCausalLM",
           "get_afmoe", "afmoe_tiny", "trinity_large_ep8",
           "pangu_moe", "PanguMoeModel", "PanguMoeForCausalLM",
           "get_pangu_moe", "pangu_moe_tiny", "pangu_ultra_moe_ep16",
           "nmt", "TransformerNMT", "BeamSearchScorer",
           "BeamSearchSampler", "get_nmt", "nmt_tiny",
           "transformer_en_de_512", "segmentation", "FCN", "DeepLabV3",
           "SegmentationMetric", "SoftmaxSegLoss", "fcn_tiny",
           "deeplab_tiny", "yolo", "YOLOv3", "YOLOv3Loss",
           "yolo3_tiny", "pose", "SimplePose", "PoseHeatmapLoss",
           "PCKMetric", "simple_pose_tiny", "rcnn", "FasterRCNN",
           "FasterRCNNLoss", "faster_rcnn_tiny"]
